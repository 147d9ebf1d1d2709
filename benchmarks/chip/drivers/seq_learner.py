"""Driver ``seq_learner``: the learner path of ``ppo_recurrent.main()`` for a policy
whose training sequences are whole episodes (a language-model policy).

Set-up makes the calls ``main()`` makes before its loop: ``compose`` ->
``build_runtime`` -> ``build_agent`` (on weights the reference made) ->
``with_clipping(optimizer)`` -> ``make_train_fn``, at the traffic mix's
``fabric.player_on_host``. It then builds the pool of rollouts from the seed
(`rollouts.py`), places the experts on the deployment's chips by the load the
pool puts on each (`place_by_load`), and scores the pool by the program's own
teacher-forced pass: old log-probs and values, as the player records them while
it acts.

The window repeats what ``main()`` does between the end of a rollout and the
next one: ``rollout_feed`` (GAE by the program's ``gae``, the split into
sequences, the transfer), ``split(rng)``, ``train_fn``, the fence ``main()``
makes while its timer is on, and the player's rebind to the new parameters
(the player shares the chip, so nothing is copied). The pool's rollouts are
taken in turn.

The program is imported and its config composed before any weight is made, so a
checkout that lacks the policy fails within seconds, with an error.

The first ``warmup_steps`` steps go through that same closure and are the steps
`check_seq.py` compares with the plain reference once the window has closed.
"""

from __future__ import annotations

import itertools
import os
import shutil
import time
from typing import Any, Dict, List

import numpy as np

from common import load_module

SEED_MOD = 2147483629  # a prime under 2**31: every --seed maps to a valid PRNG seed


def build(cell: Dict[str, Any], seed: int, rehearse: bool, t_start: float = 0.0) -> Dict[str, Any]:
    """Everything the window drives, built once: the object `run` warms up is the
    one it times and the one `check_seq.py` reads."""
    learner = load_module("drivers", "learner", cell["here"])  # its Spans, Watcher and stage are shared
    import jax
    import jax.numpy as jnp

    # the program first: a tree without this policy stops here
    from sheeprl_tpu import cli
    from sheeprl_tpu.algos.ppo_recurrent import ppo_recurrent
    from sheeprl_tpu.algos.ppo_recurrent.agent import build_agent
    from sheeprl_tpu.config import compose, instantiate
    from sheeprl_tpu.core.runtime import build_runtime
    from sheeprl_tpu.models import lm
    from sheeprl_tpu.utils.optim import with_clipping

    config, traffic = cell["config_file"], cell["traffic_file"]
    seed32 = int(seed) % SEED_MOD
    overrides = list(config["overrides"]) + list(traffic["overrides"]) + [f"seed={seed32}"]
    sizes = dict(config["sizes"])
    if rehearse:
        overrides += list(config["rehearse_overrides"])
        sizes.update(config["rehearse_sizes"])
    learner.stage("imports done", t_start)
    cfg = compose(config_name="config", overrides=overrides)
    cli._apply_global_flags(cfg)  # what cli.run_algorithm sets before main(): compile policy, timer, matmul precision
    runtime = build_runtime(cfg.fabric)
    n_envs = int(cfg.env.num_envs) * runtime.world_size
    if (n_envs, int(cfg.algo.rollout_steps)) != (int(sizes["batch"]), int(sizes["sequence"])):
        raise ValueError(f"the program's rollout is {n_envs} x {cfg.algo.rollout_steps}, the configuration's sizes say {sizes['batch']} x {sizes['sequence']}")

    # weights from the seed, on the device in one jitted call, made by the reference
    reference = load_module("reference", config["reference"], cell["here"])
    ref_sizes = reference.sizes_from(sizes)
    spec = reference.param_spec(ref_sizes)
    n_moe = sum(1 for _, ffn in reference.layer_kinds(ref_sizes) if ffn == "moe")
    placement = {"now": np.tile(np.arange(int(sizes["num_experts"]), dtype=np.int32), (n_moe, 1))}  # as the seed numbers them
    placed = jax.jit(lambda s, where: reference.place_experts(reference.make_params(spec, s), where, ref_sizes))

    def make_weights(s, where=None):
        """The seed's weights under ``where``, or under the placement of the moment: `reseed` sets it, and the
        check makes the same weights again (inside its jitted functions it hands the placement over as an
        argument: as a constant of theirs it would make every seed's program another one to compile)."""
        return placed(s, placement["now"] if where is None else where)

    weights = make_weights(jnp.int32(seed32))
    learner.stage("weights made", t_start)

    import gymnasium as gym

    vocab = int(sizes["vocab"])
    obs_space = gym.spaces.Dict(
        {"tokens": gym.spaces.Box(0, vocab - 1, (1,), np.int32), "sampled": gym.spaces.Box(0, 1, (1,), np.int32)}
    )
    agent, params, player = build_agent(runtime, (vocab,), False, cfg, obs_space, weights)
    player.params = None  # `reseed` makes the weights it places: these would lie beside them until set-up ends
    del weights, params
    tx = with_clipping(instantiate(dict(cfg.algo.optimizer))(), cfg.algo.max_grad_norm)
    obs_keys = list(cfg.algo.cnn_keys.encoder) + list(cfg.algo.mlp_keys.encoder)
    train_fn = ppo_recurrent.make_train_fn(agent, tx, cfg, runtime, obs_keys, list(cfg.algo.cnn_keys.encoder), None)
    learner.stage("build_agent and make_train_fn done", t_start)

    rollouts = load_module("", "rollouts", cell["here"])

    def score(params, tokens, actions):
        logp, _, values, aux = lm.evaluate(params, tokens, actions, agent.config, agent.dtype)
        return logp, values, aux.get("choices")

    built = {
        "cfg": cfg, "runtime": runtime, "agent": agent, "player": player, "tx": tx, "train_fn": train_fn,
        "state": {}, "reference": reference, "ref_sizes": ref_sizes, "make_weights": make_weights,
        "placement": placement, "rollouts_mod": rollouts, "score": jax.jit(score), "feed": ppo_recurrent.rollout_feed,
        "n_envs": n_envs, "sizes": sizes, "traffic": traffic, "stage": learner.stage, "learner": learner,
    }
    reseed(built, seed)
    learner.stage("state placed, pool built and scored", t_start)
    return built


def balanced_groups(load: np.ndarray, chips: int) -> np.ndarray:
    """The experts in a new order, ``chips`` consecutive groups of equal size whose loads are as even as
    a load balancer for expert parallelism makes them when it places experts on chips: heaviest expert
    first, each to the lightest group that has room; then two groups exchange an expert each for as long
    as that brings their loads closer. Within a group, by number."""
    load = np.asarray(load, np.float64)
    per = len(load) // chips
    groups: List[List[int]] = [[] for _ in range(chips)]
    for e in np.argsort(-load, kind="stable"):
        c = min((c for c in range(chips) if len(groups[c]) < per), key=lambda c: (load[groups[c]].sum(), c))
        groups[c].append(int(e))
    closer = True
    while closer:
        closer = False
        for a, b in itertools.combinations(range(chips), 2):
            gap = load[groups[a]].sum() - load[groups[b]].sum()
            left, i, j = min((abs(gap - 2 * (load[i] - load[j])), i, j) for i in groups[a] for j in groups[b])
            if left < abs(gap) - 1e-9:
                groups[a][groups[a].index(i)], groups[b][groups[b].index(j)] = j, i
                closer = True
    return np.asarray([e for g in groups for e in sorted(g)], np.int32)


def place_by_load(built: Dict[str, Any], feed):
    """The seed's weights with every expert layer's experts placed on the deployment's chips by the load
    that the pool puts on each, the first group being this chip's (its experts' outputs are renumbered
    0 to ``experts_held`` - 1; the experts' kernels are drawn alike, so only the router's outputs and the
    bias move). Layer by layer, since a layer's placement changes what reaches the next. With random
    weights and Zipf ids one chip's share of the pairs differs from seed to seed by 3% (standard
    deviation) and the grouped products' time with it, 0.58 us a pair: as drawn the mean step of six
    seeds lay 0.73% apart, placed 0.27% (my chip runs, PR 29), and the cell's bound allows a spread of
    0.5%. A deployment balances its chips too. The unevenness among the experts of one chip stays."""
    import jax.numpy as jnp

    runtime, placement, sizes = built["runtime"], built["placement"], built["sizes"]
    chips = int(sizes["num_experts"]) // int(sizes["experts_held"])
    where = np.tile(np.arange(int(sizes["num_experts"]), dtype=np.int32), (placement["now"].shape[0], 1))
    placement["now"] = where.copy()
    params = runtime.place_params(built["make_weights"](jnp.int32(built["seed32"])))
    for layer in range(where.shape[0]):
        load = np.zeros(where.shape[1])
        for tokens, actions in feed:
            load += np.bincount(np.asarray(built["score"](params, tokens, actions)[2][layer]).reshape(-1), minlength=len(load))
        where[layer] = where[layer][balanced_groups(load, chips)]
        placement["now"] = where.copy()
        del params
        params = runtime.place_params(built["make_weights"](jnp.int32(built["seed32"])))
    return params


def reseed(built: Dict[str, Any], seed: int) -> None:
    """The state as ``main()`` lays it out before its first train call, from ``seed``:
    weights (placed by load), optimizer state, key, the player's parameters, and the
    pool of rollouts scored by those weights."""
    import jax
    import jax.numpy as jnp

    seed32 = int(seed) % SEED_MOD
    runtime, state, sizes = built["runtime"], built["state"], built["sizes"]
    state.clear()
    built["seed32"] = seed32
    pool = built["rollouts_mod"].make_pool(seed32, sizes, built["traffic"])
    feed = [
        (jnp.asarray(r["tokens"][..., 0].T.astype(np.int32)), jnp.asarray(r["actions"][..., 0].T.astype(np.int32)))
        for r in pool
    ]
    params = place_by_load(built, feed)
    state.update(params=params, opt_state=runtime.place_params(built["tx"].init(params)), rng=jax.random.PRNGKey(seed32), n=0)
    built["player"].params = runtime.to_player(params)
    for rollout, (tokens, actions) in zip(pool, feed):
        logp, values, _ = built["score"](params, tokens, actions)
        rollout["logprobs"] = np.asarray(logp, np.float32).T[..., None]
        rollout["values"] = np.asarray(values, np.float32).T[..., None]
    built["pool"] = pool


def make_step(built: Dict[str, Any], spans):
    """One train call as ``main()`` makes it, on the pool's next rollout. Returns a
    small output of the call (its completion handle), the data as it reached the
    device, the call's key and its metrics."""
    import jax
    import jax.numpy as jnp

    from sheeprl_tpu.core import compile as jax_compile
    from sheeprl_tpu.utils.timer import timer

    cfg, st, pool = built["cfg"], built["state"], built["pool"]
    train_fn, player, feed, agent = built["train_fn"], built["player"], built["feed"], built["agent"]
    n_envs, host_device = built["n_envs"], built["runtime"].host_device
    # every rollout of the pool ends where its episodes end: the value after it is never used
    next_values = np.zeros((n_envs, 1), np.float32)

    def step():
        with spans("sample"):
            rollout = {k: v.copy() for k, v in pool[st["n"] % len(pool)].items()}  # as rb.to_arrays hands it over
            device_data = feed(rollout, next_values, cfg, n_envs, agent.starts_at_reset, host_device)
        st["n"] += 1
        with spans("dispatch"):
            st["rng"], key = jax.random.split(st["rng"])
            scalars = (jnp.float32(cfg.algo.clip_coef), jnp.float32(cfg.algo.ent_coef), jnp.float32(1.0))
            if "specs" not in st:  # what the traced run lowers the program's text from; made before the call donates
                st["specs"] = jax_compile.specs_of((st["params"], st["opt_state"], device_data, key, *scalars))
            st["params"], st["opt_state"], _flat, named = train_fn(
                st["params"], st["opt_state"], device_data, key, *scalars
            )
        if not timer.disabled:
            with spans("fence"):
                jax.block_until_ready(st["params"])
        with spans("player_sync"):
            player.params = st["params"]  # main() with the player on the mesh device: a rebind, no copy
        return named["Loss/policy_loss"], device_data, key, named

    return step


def run(cell, seed, seconds, trace, rehearse, devices, t_start, out_dir) -> Dict[str, Any]:
    import jax

    from sheeprl_tpu.core import compile as jax_compile

    check = load_module("", "check_seq", cell["here"])
    built = build(cell, seed, rehearse, t_start)
    learner, stage = built["learner"], built["stage"]
    spans = learner.Spans(annotate=trace)
    step = make_step(built, spans)

    # ---- warm-up: the compared steps, through the window's own call and feed
    probe = check.Probe(built)
    for i in range(int(cell["traffic_file"]["warmup_steps"])):
        _handle, device_data, key, named = step()
        jax.block_until_ready((built["state"]["params"], built["state"]["opt_state"]))
        probe.after_step(i, device_data, key, named)
    probe.finish_setup()
    del device_data, named
    stage("compared steps done", t_start)
    spans.rows.clear()
    stats0 = jax_compile.process_stats()
    trace_dir = os.path.join(out_dir, "trace")
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=options)

    # ---- the window
    watcher = learner.Watcher()
    watcher.start()
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    dispatched = 0
    if trace:
        seconds = min(seconds, float(cell["traffic_file"]["trace_seconds"]))
    last = {}
    while time.perf_counter() - t0 < seconds:
        handle, _data, _key, last = step()
        watcher.queue.put(handle)
        dispatched += 1
    jax.block_until_ready(built["state"]["params"])
    watcher.queue.put(None)
    watcher.join(timeout=120)
    if trace:
        jax.profiler.stop_trace()
    if watcher.error is not None or watcher.is_alive():
        raise RuntimeError(f"a step of the window did not complete: {watcher.error!r}")

    stats1 = jax_compile.process_stats()
    done = [t - t0 for t in watcher.done_at]
    window_s = done[-1]
    intervals = np.diff(np.asarray([0.0] + done)) * 1e3
    memory_peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)
    # the last step's metrics, the expert layers' counters among them (not the routing itself)
    counters = {k: float(v) for k, v in last.items() if np.ndim(v) == 0}
    out: Dict[str, Any] = {
        "attempted": dispatched,
        "failed": dispatched - len(done) + int(counters.get("Resilience/nonfinite_skips", 0.0) > 0),
        "steps": {"in_window": len(done), "window_s": window_s, "asked_s": seconds,
                  "longest_interval_ms": float(intervals.max()), "longest_at_step": int(intervals.argmax())},
        "end_to_end": {
            "gsteps_per_s": len(done) / window_s,
            "step_ms_p95": float(np.percentile(intervals, 95)),
            "setup_s": setup_s,
        },
        "memory_peak_bytes": memory_peak,
        "spans": [(n, a - t0, b - t0) for n, a, b in spans.rows],
        "window_s": window_s,
        "compile": {"at_window_start": stats0, "at_window_end": stats1},
        "config": cell["config_file"],
        "n_devices": len(devices),
        "counters": counters,
        "placement": built["placement"]["now"],
    }
    if trace:
        reduce = load_module("", "reduce", cell["here"])
        scopes = load_module("", "scopes", cell["here"])
        # the device events carry no scope: the compiled program's text does (scopes.py). main() calls the
        # train function through plain jit, so its text is made again here from the call's own specs; the
        # executable comes out of the persistent cache
        with open(os.path.join(trace_dir, "train.hlo.txt"), "w") as f:
            f.write(scopes.compiled_text(built["train_fn"], built["state"]["specs"]))
        out["trace"] = reduce.reduce_dir(trace_dir)
        # device self time by the scopes that the configuration's count file names
        names = load_module("", "flops", cell["here"]).scopes_of(cell["config_file"])
        out["scopes"] = scopes.reduce_dir(trace_dir, names)
        out["trace"]["breakdown"]["device_ms_a_step_by_scope"] = scopes.ms_a_step(out["scopes"])
        shutil.rmtree(trace_dir, ignore_errors=True)

    # ---- the comparison, once the window has closed, the peak is read and the program's state is freed
    stage("window closed", t_start)
    built["state"].clear()
    del step
    for k in ("player", "train_fn", "score", "agent"):
        built.pop(k)
    out["check"] = probe.compare(cell["config_file"])
    stage("compared with the reference", t_start)
    return out
