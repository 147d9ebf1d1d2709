"""The comparison that decides ``correct`` for a token-policy training cell.

Two layers, both on what the timed path itself produced at the timed sizes:

(a) the rollout feed: the tokens, actions and loss flags of the compared steps, as
    they arrived on the device, equal the seed's (``tokens_wrong``, exact);
(b) the train program: the plain reference follows the same steps from the same
    weights, rollouts and keys. Compared are each step's three losses and the
    gradient's global norm; the first gradient as the optimizer gets it (Adam's
    first moment after one step / 0.1), by its norm (``grad_gap``: worst leaf of
    a group) and element by element (``grad_diff``: all elements of a group,
    ``grad_diff_leaf``: the group's median leaf); the norm of the parameters'
    change after the last step (``delta_gap``); and the share of the first
    step's (token, slot) expert choices on which program and reference disagree
    (``route_disagree.step1``).

**The routing is followed, then compared apart.** The top-k of an expert layer
is the model's one discontinuity. With seeded random weights a token's 32 scores
lie close together: bfloat16 activations move about one choice in ten against
float32 at ``highest``, and so does float32 at the TPU's ``high`` (my chip runs,
PR 29), and a token whose choice moved differs afterwards by an expert's whole
output, which leaves the first gradient 40-65% off in every precision. So the
reference computes each compared step with the experts the program's train call
chose (``Moe/choices`` among the call's metrics) and the numbers above read the
arithmetic; the reference's own top-k, each layer's on the state that reached
it, is what ``route_disagree`` compares the program's choices with. A program
that routes wrongly fails that number, one that computes wrongly the others;
where the two sides do not even choose the same number of experts the routing
cannot be followed and the reference runs free.

Groups: ``mixers`` (convolution and attention weights and the norm before them),
``experts`` (the dense FFN, the experts held and the norm before them),
``router``, ``embed`` (embedding = output head, final norm) and ``critic``. The
expert bias has no gradient and is left out.

`Probe` takes the program's readings during set-up and runs the reference only
once the window has closed and the program's state is freed. Limits are the
configuration file's ``limits``; how they were set is in PERF.md. The arithmetic
on two sets of readings (`diff_norms`, `group_diff`, `worst_gap`, `judge`) is
``check.py``'s own.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional

import numpy as np

from common import HERE, load_module

check = load_module("", "check", HERE)
judge = check.judge

ADAM_B1 = check.ADAM_B1
GROUPS = ("mixers", "experts", "router", "embed", "critic")
LOSSES = {
    "policy": "Loss/policy_loss", "value": "Loss/value_loss", "entropy": "Loss/entropy_loss",
    "grad_norm": "Grads/global_norm",
}
# a loss is compared against its own size or this floor, whichever is larger: the policy loss is a
# mean of advantages of either sign and can lie anywhere near zero
LOSS_FLOOR = {"policy": 0.05, "value": 1e-3, "entropy": 1e-3, "grad_norm": 1e-6}
FEED_KEYS = ("tokens", "actions", "sampled")


def _grouped(reference, leaves: Dict[str, Any]) -> Dict[str, Any]:
    """``keystr path -> value`` renamed ``group + path``; leaves of no group (the expert bias) dropped."""
    return {reference.group_of(k) + k: v for k, v in leaves.items() if reference.group_of(k) is not None}


def first_moments(opt_state) -> Dict[str, Any]:
    """``leaf path -> mu`` out of the program's optimizer state (an optax chain; Adam's
    first moment is the subtree reached through an attribute ``mu``)."""
    import jax

    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(opt_state)[0]:
        name = jax.tree_util.keystr(path)
        if ".mu" in name:
            out[name.split(".mu", 1)[1]] = leaf
    return out


def route_disagree(prog: np.ndarray, ref: np.ndarray) -> float:
    """Share of the reference's (token, slot) choices [layers, N, k] that are not among the program's of that token."""
    if prog.shape[:2] != ref.shape[:2]:
        return 1.0
    hit = (ref[..., :, None] == prog[..., None, :]).any(axis=-1)
    return float(1.0 - hit.mean())


def in_feed_order(chosen: np.ndarray, key, n_seq: int) -> np.ndarray:
    """The train call's choices [layers, n_seq * T, k] with the sequences back in the order they were fed in.
    ``ppo_recurrent.train`` spends its key on the order of the sequences in its one minibatch
    (``permutation(split(key, update_epochs)[0], n_seq)``) and reports its routing in that order."""
    import jax

    order = np.asarray(jax.random.permutation(jax.random.split(key, 1)[0], n_seq))
    by_seq = chosen.reshape(chosen.shape[0], n_seq, -1, chosen.shape[-1])
    out = np.empty_like(by_seq)
    out[:, order] = by_seq
    return out.reshape(chosen.shape)


def negligible_leaves(ref_grads: Dict[str, float]) -> List[str]:
    skip = []
    for group in GROUPS:
        names = [n for n in ref_grads if n.startswith(group + "[")]
        median = float(np.median([ref_grads[n] for n in names]))
        skip += [n for n in names if ref_grads[n] < check.NEGLIGIBLE_GRAD * median]
    return skip


class Probe:
    """The program's readings of the compared steps, then the comparison."""

    def __init__(self, built: Dict[str, Any], compiled: Optional[Dict[Any, Any]] = None):
        # the reference's jitted step by control: `calibrate_seq.py` hands every probe the same dict, so the
        # reference is traced and lowered once a control and not once a reading
        self.compiled = {} if compiled is None else compiled
        self.reference = built["reference"]
        self.sizes = built["ref_sizes"]
        self.make_weights = built["make_weights"]
        self.where = built["placement"]["now"]  # the experts' placement these weights were made under
        self.seed32 = built["seed32"]
        self.pool = built["pool"]
        self.state = built["state"]
        self.choices: List[Optional[np.ndarray]] = []
        self.batches: List[Any] = []
        self.keys: List[Any] = []
        self.losses: List[Dict[str, Any]] = []
        self.grad_norms: Optional[Dict[str, Any]] = None
        self.first: Optional[Dict[str, np.ndarray]] = None
        self.delta_norms: Optional[Dict[str, Any]] = None
        self.n_steps = 0

    # ---- during set-up
    def after_step(self, i: int, device_data, key, named) -> None:
        import jax
        import jax.numpy as jnp

        self.batches.append(device_data)
        self.keys.append(key)
        self.losses.append({k: named[v] for k, v in LOSSES.items()})
        n_seq = next(iter(device_data.values())).shape[1]
        self.choices.append(in_feed_order(np.asarray(named["Moe/choices"]), key, n_seq) if "Moe/choices" in named else None)
        self.n_steps = i + 1
        if i == 0:
            moments = first_moments(self.state["opt_state"])
            self.grad_norms = jax.jit(
                lambda m: {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)))) for k, v in m.items()}
            )(moments)
            self.first = check.first_gradient(moments)  # a copy on the host: the next step donates them

    def finish_setup(self) -> None:
        """After the last compared step: the change of the parameters since the seed's
        weights, by a jitted reduction that makes the weights again instead of keeping a copy."""
        import jax
        import jax.numpy as jnp

        reference, make_weights = self.reference, self.make_weights

        def delta(p, seed, where):
            return reference.leaf_norms(jax.tree_util.tree_map(jnp.subtract, p, make_weights(seed, where)))

        self.delta_norms = jax.jit(delta)(self.state["params"], jnp.int32(self.seed32), self.where)
        self.state = None

    # ---- once the window has closed
    def tokens_wrong(self) -> int:
        """Elements of the compared steps' tokens, actions and loss flags that differ from
        the seed's rollouts. Also builds the reference's feed, [B, T] a key."""
        wrong = 0
        self.feed = []
        for i, batch in enumerate(self.batches):
            got = {k: np.asarray(v)[..., 0].T for k, v in batch.items()}  # [n_seq, T]
            want = self.pool[i % len(self.pool)]
            for k in FEED_KEYS:
                w = want[k][..., 0].T
                wrong += int(np.sum(got[k] != w)) if got[k].shape == w.shape else int(w.size)
            self.feed.append({
                "tokens": got["tokens"].astype(np.int32), "actions": got["actions"].astype(np.int32),
                "logprobs": got["logprobs"], "advantages": got["advantages"], "returns": got["returns"],
                "mask": got["mask"] * got["sampled"],
            })
        return wrong

    def reference_readings(self, quant: Optional[Callable] = None) -> Dict[str, Any]:
        """The reference over the compared steps. ``quant`` makes it the control."""
        import jax
        import jax.numpy as jnp

        ref, s = self.reference, self.sizes

        def one(state, batch, key, forced):
            state, out = ref.train_step(state, batch, key, s, quant=quant, forced=forced)
            return state, out["losses"], ref.leaf_norms(out["grads"]), out["choices"]

        def followed(chosen):
            """The program's choices of a step, where the reference can follow them: same layers, tokens and experts a token."""
            n_moe = sum(1 for _, ffn in ref.layer_kinds(s) if ffn == "moe")
            want = (n_moe, self.feed[0]["tokens"].size, int(s["num_experts_per_tok"]))
            return jnp.asarray(chosen) if chosen is not None and chosen.shape == want else None

        def delta(state, seed, where):
            return ref.leaf_norms(jax.tree_util.tree_map(jnp.subtract, state["params"], self.make_weights(seed, where)))

        with jax.default_matmul_precision("highest"):
            if quant not in self.compiled:
                self.compiled[quant] = jax.jit(one, donate_argnums=0)
            step = self.compiled[quant]
            seed = jnp.int32(self.seed32)
            state = jax.jit(lambda sd, where: ref.init_state(self.make_weights(sd, where)))(seed, self.where)
            losses, grads, first, choices = [], None, None, None
            for i in range(self.n_steps):
                batch = {k: jnp.asarray(v) for k, v in self.feed[i].items()}
                state, loss, g, chosen = step(state, batch, self.keys[i], followed(self.choices[i]))
                losses.append(check._to_host(loss))
                if i == 0:
                    grads = check._to_host(g)
                    choices = None if chosen is None else np.asarray(chosen)
                    first = check.first_gradient({
                        jax.tree_util.keystr(p): v for p, v in jax.tree_util.tree_flatten_with_path(state["opt"]["mu"])[0]
                    })
            deltas = check._to_host(jax.jit(delta)(state, seed, self.where))
            del state
        # the reference reports the norm of the raw gradient; the optimizer gets it clipped, as the program's moments have it
        clip = float(s["max_grad_norm"])
        scale = min(1.0, clip / max(losses[0]["grad_norm"], 1e-30)) if clip > 0 else 1.0
        return {
            "losses": losses, "grads": _grouped(ref, {k: v * scale for k, v in grads.items()}),
            "deltas": _grouped(ref, deltas), "first": _grouped(ref, first), "choices": choices,
        }

    def program_readings(self) -> Dict[str, Any]:
        ref = self.reference
        return {
            "losses": [check._to_host(l) for l in self.losses],
            "grads": _grouped(ref, {k: float(v) / (1.0 - ADAM_B1) for k, v in self.grad_norms.items()}),
            "deltas": _grouped(ref, check._to_host(self.delta_norms)),
            "first": _grouped(ref, self.first),
            "choices": self.choices[0],
        }

    def compare(self, config: Dict[str, Any]) -> Dict[str, Any]:
        numbers: Dict[str, float] = {"tokens_wrong": float(self.tokens_wrong())}
        numbers.update(gaps(self.program_readings(), self.reference_readings()))
        return judge(numbers, config.get("limits", {}))


def gaps(prog: Dict[str, Any], ref: Dict[str, Any], leaves: Optional[Dict[str, Any]] = None) -> Dict[str, float]:
    """The numbers compared, from two sets of readings (``leaves``: their `diff_norms`, where already made)."""
    out: Dict[str, float] = {}
    for name in LOSSES:
        for i, (p, r) in enumerate(zip(prog["losses"], ref["losses"])):
            gap = abs(p[name] - r[name]) / max(abs(r[name]), LOSS_FLOOR[name])
            out[f"{name}.step{i + 1}"] = gap if gap == gap else float("inf")
    skip = negligible_leaves(ref["grads"])
    leaves = leaves or check.diff_norms(prog["first"], ref["first"])
    for group in GROUPS:
        out[f"grad_gap.{group}"] = check.worst_gap(prog["grads"], ref["grads"], group)["value"]
        out[f"delta_gap.{group}"] = check.worst_gap(prog["deltas"], ref["deltas"], group, skip=skip)["value"]
        diff = check.group_diff(leaves, group)
        out[f"grad_diff.{group}"], out[f"grad_diff_leaf.{group}"] = diff["all"], diff["leaf"]
    if prog.get("choices") is not None and ref.get("choices") is not None:
        out["route_disagree.step1"] = route_disagree(prog["choices"], ref["choices"])
    return out
