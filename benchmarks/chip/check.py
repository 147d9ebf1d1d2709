"""The comparison that decides ``correct`` for a training cell.

Two layers, both on what the timed path itself produced at the timed sizes:

(a) buffer sample and H2D: every row of the compared steps' batches, as it
    arrived on the device, equals the row the seed put into the buffer
    (``rows_wrong``, exact);
(b) the train program: the plain reference follows the same steps from the same
    weights, rows and keys. Compared are each step's three losses, the norm of
    the first gradient as the optimizer gets it (from Adam's first moment after
    one step) and the norm of the parameters' change after the last step, both
    by the worst leaf: the gap between the program's norm and the reference's,
    against the reference's norm of that leaf or of the median leaf of its
    group, whichever is larger. And, the number that tells a lower precision
    from the configuration's: the first gradient itself, element by element,
    as the norm of its difference from the reference's over the reference's
    norm, over all elements of a group (``grad_diff``) and for its median leaf
    (``grad_diff_leaf``).

`Probe` takes the program's readings during set-up (small jitted reductions, no
copy of the state is kept) and runs the reference only once the window has
closed. Limits are the configuration file's ``limits``; how they were set is in
PERF.md.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional

import numpy as np

ADAM_B1 = 0.9  # the first moment after one step is (1 - b1) * gradient
GROUPS = ("world_model", "actor", "critic")
# the reference's name of each per-step reading -> the program's own metric of the train call
LOSSES = {
    "world_model": "Loss/world_model_loss", "policy": "Loss/policy_loss", "value": "Loss/value_loss",
    "observation": "Loss/observation_loss", "reward": "Loss/reward_loss", "continue": "Loss/continue_loss",
    "kl": "State/kl", "post_entropy": "State/post_entropy", "prior_entropy": "State/prior_entropy",
    "grad_norm.world_model": "Grads/world_model", "grad_norm.actor": "Grads/actor", "grad_norm.critic": "Grads/critic",
}
NEGLIGIBLE_GRAD = 1e-3  # of the median leaf's: such a leaf moves under Adam by round-off alone


def _to_host(tree) -> Dict[str, float]:
    return {k: float(v) for k, v in tree.items()}


def first_moments(opt_states) -> Dict[str, Any]:
    """``group + leaf path -> mu`` out of the program's optimizer states (optax
    chains; the Adam moment is the subtree reached through an attribute ``mu``)."""
    import jax

    out = {}
    for group, state in zip(GROUPS, opt_states):
        for path, leaf in jax.tree_util.tree_flatten_with_path(state)[0]:
            name = jax.tree_util.keystr(path)
            if ".mu" in name:
                out[group + name.split(".mu", 1)[1]] = leaf
    return out


def first_moment_norms(opt_states) -> Dict[str, Any]:
    import jax.numpy as jnp

    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)))) for k, v in first_moments(opt_states).items()}


def first_gradient(moments: Dict[str, Any]) -> Dict[str, np.ndarray]:
    """Adam's first moments after one step, on the host, as the gradient the optimizer got."""
    import jax

    return {k: np.asarray(v, np.float32) / (1.0 - ADAM_B1) for k, v in jax.device_get(moments).items()}


def diff_norms(prog: Dict[str, np.ndarray], ref: Dict[str, np.ndarray]) -> Dict[str, Any]:
    """``leaf -> (|prog - ref|^2, |ref|^2)`` of the first gradient, element by element."""
    if set(prog) != set(ref):
        raise ValueError(f"program and reference disagree on the leaves, e.g. {sorted(set(prog) ^ set(ref))[:4]}")
    out = {}
    for n, r in ref.items():
        d = prog[n] - r
        out[n] = (float(np.sum(d * d, dtype=np.float64)), float(np.sum(r * r, dtype=np.float64)))
    return out


def group_diff(leaves: Dict[str, Any], group: str) -> Dict[str, float]:
    """The first gradient's difference over ``group``: of all its elements together
    (``all``: the large kernels weigh most), and of its median leaf (``leaf``: every
    leaf weighs alike, which reads steadier from seed to seed). NaN counts as worst."""
    pairs = [v for n, v in leaves.items() if n.startswith(group + "[")]
    whole = float(np.sqrt(sum(a for a, _ in pairs) / max(sum(b for _, b in pairs), 1e-60)))
    floor = (NEGLIGIBLE_GRAD * float(np.median([b for _, b in pairs]) ** 0.5)) ** 2  # as `negligible_leaves`
    leaf = float(np.median([np.sqrt(a / b) for a, b in pairs if b > floor and b > 0.0]))
    return {"all": whole if whole == whole else float("inf"), "leaf": leaf if leaf == leaf else float("inf")}


def grouped_norms(reference, trees: Dict[str, Any]) -> Dict[str, Any]:
    return {g + k: v for g in GROUPS for k, v in reference.leaf_norms(trees[g]).items()}


def worst_gap(prog: Dict[str, float], ref: Dict[str, float], group: str, skip=()) -> Dict[str, Any]:
    """Worst leaf of ``group``: |prog - ref| / max(ref, median ref of the group)."""
    if set(prog) != set(ref):
        missing = sorted(set(prog) ^ set(ref))[:4]
        raise ValueError(f"program and reference disagree on the leaves, e.g. {missing}")
    names = [n for n in ref if n.startswith(group + "[")]
    median = float(np.median([ref[n] for n in names]))
    worst, where = 0.0, ""
    for n in names:
        if n in skip:
            continue
        gap = abs(prog[n] - ref[n]) / max(ref[n], median, 1e-30)
        if not gap <= worst:  # NaN counts as worst
            worst, where = (gap if gap == gap else float("inf")), n
    return {"value": worst, "leaf": where}


def negligible_leaves(ref_grads: Dict[str, float]) -> List[str]:
    skip = []
    for group in GROUPS:
        names = [n for n in ref_grads if n.startswith(group + "[")]
        median = float(np.median([ref_grads[n] for n in names]))
        skip += [n for n in names if ref_grads[n] < NEGLIGIBLE_GRAD * median]
    return skip


class Probe:
    """The program's readings of the compared steps, then the comparison."""

    def __init__(self, built: Dict[str, Any]):
        self.reference = built["reference"]
        self.sizes = built["ref_sizes"]
        self.make_weights = built["make_weights"]
        self.seed32 = built["seed32"]
        self.rows = built["rows"]
        self.rows_mod = built["rows_mod"]
        self.state = built["state"]
        self.batches: List[Any] = []
        self.keys: List[Any] = []
        self.losses: List[Dict[str, Any]] = []
        self.grad_norms: Optional[Dict[str, Any]] = None
        self.first: Optional[Dict[str, np.ndarray]] = None
        self.delta_norms: Optional[Dict[str, Any]] = None
        self.n_steps = 0

    # ---- during set-up
    def after_step(self, i: int, batches, key, named) -> None:
        import jax

        self.batches.append(batches)
        self.keys.append(key)
        self.losses.append({k: named[v] for k, v in LOSSES.items()})
        self.n_steps = i + 1
        if i == 0:
            self.grad_norms = jax.jit(first_moment_norms)(self.state["opt_states"])
            self.first = first_gradient(first_moments(self.state["opt_states"]))  # a copy on the host: the next step donates them

    def finish_setup(self) -> None:
        """After the last compared step: the change of the parameters since the
        seed's weights, which the jitted reduction makes again instead of keeping
        a copy beside the program."""
        import jax
        import jax.numpy as jnp

        reference, make_weights = self.reference, self.make_weights

        def delta(p, seed):
            w = make_weights(seed)
            return grouped_norms(reference, {g: jax.tree_util.tree_map(jnp.subtract, p[g], w[g]) for g in GROUPS})

        params = {g: self.state["params"][g] for g in GROUPS}
        self.delta_norms = jax.jit(delta)(params, jnp.int32(self.seed32))
        self.state = None

    # ---- once the window has closed
    def rows_wrong(self) -> int:
        """Rows of the compared batches that differ from the seed's rows, or whose
        sequence is not consecutive rows of the buffer. Also builds the reference's feed."""
        wrong = 0
        self.feed = []
        n = next(iter(self.rows.values())).shape[0]
        for batches in self.batches:
            got = {k: np.asarray(v)[0] for k, v in batches.items()}  # [T, B, ...]
            ids = self.rows_mod.row_ids(got["rgb"])  # [T, B]
            wrong += int(np.sum((ids[1:] - ids[:-1]) % n != 1))
            bad = np.zeros(ids.shape, dtype=bool)
            want = {}
            for k, v in got.items():
                want[k] = self.rows[k][ids % n, 0]
                bad |= np.any((want[k] != v).reshape(*ids.shape, -1), axis=-1)
            wrong += int(bad.sum())
            self.feed.append(want)
        return wrong

    def reference_readings(self, quant: Optional[Callable] = None) -> Dict[str, Any]:
        """The reference over the compared steps. ``quant`` makes it the control."""
        import jax
        import jax.numpy as jnp

        ref, s = self.reference, self.sizes
        sample_dtype = jnp.bfloat16 if "bf16" in self.precision else jnp.float32

        def one(state, batch, key):
            state, out = ref.train_step(state, batch, key, s, quant=quant, sample_dtype=sample_dtype)
            return state, out["losses"], grouped_norms(ref, out["grads"])

        def delta(state, seed):
            w = self.make_weights(seed)
            return grouped_norms(
                ref, {g: jax.tree_util.tree_map(jnp.subtract, state["params"][g], w[g]) for g in GROUPS}
            )

        step = jax.jit(one, donate_argnums=0)
        seed = jnp.int32(self.seed32)
        state = jax.jit(lambda sd: ref.init_state(self.make_weights(sd)))(seed)
        losses, grads = [], None
        for i in range(self.n_steps):
            batch = self.feed[i]
            state, loss, g = step(state, {k: jnp.asarray(v) for k, v in batch.items()}, self.keys[i])
            losses.append(_to_host(loss))
            if i == 0:
                grads = _to_host(g)
                first = first_gradient({
                    group + jax.tree_util.keystr(p): v for group in GROUPS
                    for p, v in jax.tree_util.tree_flatten_with_path(state["opt"][group]["mu"])[0]
                })
        deltas = _to_host(jax.jit(delta)(state, seed))
        del state
        return {"losses": losses, "grads": grads, "deltas": deltas, "first": first}

    def program_readings(self) -> Dict[str, Any]:
        return {
            "losses": [_to_host(l) for l in self.losses],
            "grads": {k: float(v) / (1.0 - ADAM_B1) for k, v in self.grad_norms.items()},
            "deltas": _to_host(self.delta_norms),
            "first": self.first,
        }

    def compare(self, config: Dict[str, Any]) -> Dict[str, Any]:
        self.precision = config["precision"]
        limits = config.get("limits", {})
        numbers: Dict[str, float] = {"rows_wrong": float(self.rows_wrong())}
        numbers.update(gaps(self.program_readings(), self.reference_readings()))
        return judge(numbers, limits)


def gaps(prog: Dict[str, Any], ref: Dict[str, Any], leaves: Optional[Dict[str, Any]] = None) -> Dict[str, float]:
    """The numbers compared, from two sets of readings (``leaves``: their `diff_norms`, where already made)."""
    out: Dict[str, float] = {}
    for name in LOSSES:
        for i, (p, r) in enumerate(zip(prog["losses"], ref["losses"])):
            out[f"{name}.step{i + 1}"] = abs(p[name] - r[name]) / max(abs(r[name]), 1e-6)
    skip = negligible_leaves(ref["grads"])
    for group in GROUPS:
        out[f"grad_gap.{group}"] = worst_gap(prog["grads"], ref["grads"], group)["value"]
        out[f"delta_gap.{group}"] = worst_gap(prog["deltas"], ref["deltas"], group, skip=skip)["value"]
    leaves = leaves or diff_norms(prog["first"], ref["first"])
    for group in GROUPS:
        diff = group_diff(leaves, group)
        out[f"grad_diff.{group}"], out[f"grad_diff_leaf.{group}"] = diff["all"], diff["leaf"]
    return out


def judge(numbers: Dict[str, float], limits: Dict[str, Any]) -> Dict[str, Any]:
    """``correct`` is every number that has a limit at or under it (NaN fails).
    The configuration's ``limits`` names what is printed; a null limit is a number
    that is shown and not compared (PERF.md says why it has no upper reading)."""
    missing = [k for k in limits if k not in numbers]
    compared = {k: {"value": numbers[k], "limit": limits[k]} for k in limits if k in numbers}
    correct = all(v["value"] <= v["limit"] for v in compared.values() if v["limit"] is not None)
    return {"correct": bool(correct and not missing), "compared": compared, "missing": missing}
