"""CLI: config composition, validation, dispatch to algorithm entrypoints.

Reference: sheeprl/cli.py (run :358, run_algorithm :60, eval_algorithm :202,
evaluation :369, registration :408, check_configs :271, resume_from_checkpoint :23,
reproducible :187). Structural difference: no ``fabric.launch`` process fork — JAX is
single-controller SPMD, so the entrypoint is called directly and parallelism lives in
the mesh (multi-host runs launch this same CLI once per host with
``fabric.multihost=True``).
"""

from __future__ import annotations

import importlib
import os
import sys
import time
import warnings
from typing import Any, Dict, List, Optional, Sequence

from sheeprl_tpu.config import ConfigError, compose
from sheeprl_tpu.core.runtime import Runtime, build_runtime, seed_everything
from sheeprl_tpu.utils.checkpoint import CheckpointCallback, load_state
from sheeprl_tpu.utils.registry import algorithm_registry, evaluation_registry
from sheeprl_tpu.utils.utils import dotdict, print_config

# Algorithm modules are imported lazily by name; this manifest mirrors the reference's
# eager imports in sheeprl/__init__.py:18-50 and keeps `available_agents` cheap.
KNOWN_ALGO_MODULES = [
    "a2c",
    "dream_and_ponder",
    "dreamer_v1",
    "dreamer_v2",
    "dreamer_v3",
    "droq",
    "p2e_dv1",
    "p2e_dv2",
    "p2e_dv3",
    "ppo",
    "ppo_recurrent",
    "sac",
    "sac_ae",
]


def _import_algorithms() -> None:
    for mod in KNOWN_ALGO_MODULES:
        try:
            importlib.import_module(f"sheeprl_tpu.algos.{mod}")
        except ModuleNotFoundError:
            pass


def resume_from_checkpoint(cfg: dotdict) -> dotdict:
    """Merge the checkpoint's sidecar config, preserving run-identity keys.

    Reference: sheeprl/cli.py:23-57.
    """
    if cfg.checkpoint.resume_from is None:
        return cfg
    ckpt_path = os.path.abspath(cfg.checkpoint.resume_from)
    # sharded checkpoints are *.ckpt DIRECTORIES (utils/ckpt_sharded.py)
    if not (os.path.isfile(ckpt_path) or os.path.isdir(ckpt_path)):
        raise ValueError(f"The checkpoint to resume from does not exist: {ckpt_path}")
    old_cfg_path = os.path.join(os.path.dirname(ckpt_path), os.pardir, "config.yaml")
    if not os.path.isfile(old_cfg_path):
        raise RuntimeError(f"The config file of the checkpoint to resume from does not exist: {old_cfg_path}")
    import yaml

    with open(old_cfg_path) as f:
        old_cfg = dotdict(yaml.safe_load(f))
    if old_cfg.env.id != cfg.env.id:
        raise ValueError(
            f"This experiment is run with a different environment from the one of the experiment you want to restart. "
            f"Got '{cfg.env.id}', when '{old_cfg.env.id}' is expected."
        )
    if old_cfg.algo.name != cfg.algo.name:
        raise ValueError(
            f"This experiment is run with a different algorithm from the one of the experiment you want to restart. "
            f"Got '{cfg.algo.name}', when '{old_cfg.algo.name}' is expected."
        )
    merged = dotdict(old_cfg)
    merged.checkpoint = cfg.checkpoint
    merged.checkpoint.resume_from = ckpt_path
    merged.run_name = cfg.run_name
    merged.root_dir = cfg.root_dir
    merged.seed = cfg.seed
    merged.fabric = cfg.fabric
    # Fault-tolerance and health knobs describe the RESUMING environment
    # (deadlines, restart budgets, a test run's stop_after_iters, sentinel
    # thresholds), not the experiment identity — always take the new
    # invocation's values over the sidecar's.
    if cfg.get("fault_tolerance") is not None:
        merged.fault_tolerance = cfg.fault_tolerance
    if cfg.get("health") is not None:
        merged.health = cfg.health
    # Explicitly-preserved dotted keys: the population controller's
    # exploit/explore step resumes a trial from a PEER's checkpoint with
    # perturbed hyperparameters; without this hook the sidecar merge would
    # silently swallow those overrides and every resow would be a no-op clone.
    from sheeprl_tpu.utils.utils import get_nested, set_nested

    for key in cfg.checkpoint.get("resume_preserve") or []:
        set_nested(merged, str(key), get_nested(cfg, str(key)))
    return merged


def check_configs(cfg: dotdict) -> None:
    """Config validation (reference: sheeprl/cli.py:271-345)."""
    algo_name = cfg.algo.name
    decoupled = False
    entry = _find_entrypoint(algo_name)
    if entry is not None:
        decoupled = entry["decoupled"]
    if decoupled and cfg.fabric.devices in (1, "1"):
        raise RuntimeError(f"The decoupled version of {algo_name} requires at least 2 devices/processes to run")
    if cfg.get("num_threads", 1) < 1:
        raise ValueError(f"num_threads must be >= 1, got {cfg.num_threads}")
    if cfg.metric.log_level not in (0, 1):
        raise ValueError(f"metric.log_level must be 0 or 1, got {cfg.metric.log_level}")
    if "precision" in cfg.fabric and cfg.fabric.precision in ("16-true",):
        warnings.warn("fp16-true is unstable on TPU; prefer bf16-mixed", UserWarning)


def check_configs_evaluation(cfg: dotdict) -> None:
    if cfg.float32_matmul_precision not in ("highest", "high", "default", "medium"):
        raise ValueError(
            "Invalid value '{}' for the 'float32_matmul_precision' parameter.".format(cfg.float32_matmul_precision)
        )
    if cfg.checkpoint_path is None:
        raise ValueError("You must specify the evaluation checkpoint path")


def _find_entrypoint(algo_name: str) -> Optional[Dict[str, Any]]:
    for module, implementations in algorithm_registry.items():
        for algo in implementations:
            if algo["name"] == algo_name:
                return {"module": module, **algo}
    return None


def _apply_global_flags(cfg: dotdict, plane: str = "train") -> None:
    import jax

    from sheeprl_tpu.core import compile as jax_compile
    from sheeprl_tpu.telemetry import trace
    from sheeprl_tpu.utils.timer import timer

    # Compile-management policy (retrace guard, AOT switch, persistent-cache
    # knobs) must be live before the first trace of the run.
    jax_compile.configure(cfg)

    # Span tracer: an inherited SHEEPRL_TPU_TRACE env var wins over config —
    # the orchestrator (or an operator) sets it to join child processes into
    # one trace id, and a sidecar config must not sever that.
    tel_cfg = cfg.get("metric", {}).get("telemetry") if "metric" in cfg else None
    if tel_cfg and bool(tel_cfg.get("trace", False)) and not os.environ.get(trace.ENV_VAR):
        trace.configure(plane=plane, capacity=int(tel_cfg.get("capacity", 16384)))
    # Spans follow a profiler capture whether or not a tracer is configured:
    # trace.py never imports jax, so the plane that has it hands the probe over.
    trace.follow_captures(jax.profiler.TraceAnnotation)

    # Compiled-program ledger: same env-wins contract as the tracer. With no
    # explicit path the train loops default it into the run's log dir.
    if tel_cfg and tel_cfg.get("programs"):
        from sheeprl_tpu.telemetry import programs as tel_programs

        tel_programs.configure_default(str(tel_cfg["programs"]))

    # Reference cli.py:161. The train loops fence device work ONLY when timing
    # (block_until_ready is a synchronous host<->device round trip per train
    # call), so a miswired flag serializes every iteration.
    if "metric" in cfg:
        timer.disabled = cfg.metric.get("log_level", 1) == 0 or bool(cfg.metric.get("disable_timer", False))
    precision_map = {"highest": "highest", "high": "high", "default": "default", "medium": "default"}
    jax.config.update(
        "jax_default_matmul_precision", precision_map.get(cfg.get("float32_matmul_precision", "high"), "high")
    )
    if cfg.get("jax_deterministic_ops", False):
        os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + " --xla_gpu_deterministic_ops=true"


def run_algorithm(cfg: dotdict) -> None:
    """Lookup + dispatch (reference: sheeprl/cli.py:60-199)."""
    _import_algorithms()
    entry = _find_entrypoint(cfg.algo.name)
    if entry is None:
        raise RuntimeError(f"Given the algorithm named '{cfg.algo.name}', no entrypoint has been registered")
    module = entry["module"]
    task = importlib.import_module(f"{module}.{entry['name']}")
    command = getattr(task, entry["entrypoint"])

    # Exploration -> finetuning handoff (reference cli.py:117-148): load the
    # exploration run's sidecar config and pin the env settings to it.
    kwargs: Dict[str, Any] = {}
    if "finetuning" in entry["name"]:
        import yaml

        ckpt_path = cfg.checkpoint.get("exploration_ckpt_path")
        if not ckpt_path:
            raise ValueError(
                "You must specify checkpoint.exploration_ckpt_path to finetune an exploration checkpoint"
            )
        ckpt_path = os.path.abspath(ckpt_path)
        expl_cfg_path = os.path.join(os.path.dirname(ckpt_path), os.pardir, "config.yaml")
        if not os.path.isfile(expl_cfg_path):
            raise RuntimeError(f"The config file of the exploration checkpoint does not exist: {expl_cfg_path}")
        with open(expl_cfg_path) as f:
            exploration_cfg = dotdict(yaml.safe_load(f))
        if exploration_cfg.env.id != cfg.env.id:
            raise ValueError(
                "This experiment is run with a different environment from the one of the exploration "
                f"you want to finetune. Got '{cfg.env.id}', but the environment used during exploration "
                f"was {exploration_cfg.env.id}."
            )
        kwargs["exploration_cfg"] = exploration_cfg
        cfg.checkpoint.exploration_ckpt_path = ckpt_path
        for env_key in (
            "frame_stack",
            "screen_size",
            "action_repeat",
            "grayscale",
            "clip_rewards",
            "frame_stack_dilation",
            "max_episode_steps",
            "reward_as_observation",
        ):
            if env_key in exploration_cfg.env:
                cfg.env[env_key] = exploration_cfg.env[env_key]

    utils = importlib.import_module(f"{module}.utils")
    # Prune metric keys the algorithm does not produce (reference cli.py:151-165)
    keys_to_remove = []
    if cfg.metric.log_level > 0 and "aggregator" in cfg.metric:
        aggregator_keys = getattr(utils, "AGGREGATOR_KEYS", set())
        keys_to_remove = [k for k in cfg.metric.aggregator.metrics.keys() if k not in aggregator_keys]
        for k in keys_to_remove:
            cfg.metric.aggregator.metrics.pop(k, None)
    # Prune model-manager models (reference cli.py:166-181)
    models_keys = set(getattr(utils, "MODELS_TO_REGISTER", set()))
    if "models" in cfg.model_manager:
        for k in list(cfg.model_manager.models.keys()):
            if k not in models_keys:
                cfg.model_manager.models.pop(k, None)

    checkpointer = None
    if cfg.checkpoint.get("sharded"):
        # Async elastic sharded checkpointing: the training thread pays only
        # the D2H snapshot; shard write/commit/certify/GC run on the writer
        # thread (howto/fault_tolerance.md "Sharded checkpoints & emergency
        # recovery"). Multihost drivers construct their own checkpointer with
        # a control plane; the CLI path covers the single-process world.
        from sheeprl_tpu.utils.ckpt_sharded import ShardedCheckpointer

        checkpointer = ShardedCheckpointer(process_index=0, world=1)
    callbacks = [CheckpointCallback(keep_last=cfg.checkpoint.keep_last, checkpointer=checkpointer)]
    runtime = build_runtime(cfg.fabric, extra_callbacks=[])
    runtime.callbacks = callbacks
    seed_everything(cfg.seed)
    _apply_global_flags(cfg)
    if runtime.is_global_zero:
        print_config(cfg)
    try:
        command(runtime, cfg, **kwargs)
    finally:
        for cb in callbacks:
            flush = getattr(cb, "flush", None)
            if flush is not None:
                flush()  # drain in-flight async shard writes before exit
        if checkpointer is not None:
            checkpointer.close()


def eval_algorithm(cfg: dotdict) -> None:
    """Evaluation dispatch (reference: sheeprl/cli.py:202-268)."""
    _import_algorithms()
    cfg.run_test = True
    entry = _find_entrypoint(cfg.algo.name)
    if entry is None:
        raise RuntimeError(f"Given the algorithm named '{cfg.algo.name}', no entrypoint has been registered")
    module = entry["module"]
    evals = evaluation_registry.get(module, [])
    eval_entry = next((e for e in evals if e["name"] == entry["name"]), None)
    if eval_entry is None:
        raise RuntimeError(f"No evaluation has been registered for the algorithm named '{cfg.algo.name}'")
    task = importlib.import_module(f"{module}.{eval_entry['evaluation_file']}")
    command = getattr(task, eval_entry["entrypoint"])
    runtime = Runtime(accelerator=cfg.fabric.get("accelerator", "auto"), devices=1, precision=cfg.fabric.precision)
    seed_everything(cfg.seed)
    _apply_global_flags(cfg)
    state = load_state(cfg.checkpoint_path)
    command(runtime, cfg, state)


def evaluation(overrides: Optional[Sequence[str]] = None) -> None:
    """`sheeprl-eval` entry: boot entirely from the checkpoint's sidecar config.

    Reference: sheeprl/cli.py:369-405.
    """
    overrides = list(overrides if overrides is not None else sys.argv[1:])
    cli_cfg: Dict[str, Any] = {}
    for ov in overrides:
        key, _, value = ov.partition("=")
        import yaml as _yaml

        cli_cfg[key.strip()] = _yaml.safe_load(value)
    ckpt_path = cli_cfg.get("checkpoint_path")
    if ckpt_path is None:
        raise ConfigError("You must specify checkpoint_path=<path> for evaluation")
    ckpt_path = os.path.abspath(ckpt_path)
    # Prefer a CERTIFIED sibling over an uncertified request: the requested file
    # may be a mid-rollback or corrupt artifact the health ladder already
    # refused to vouch for. prefer_certified=False keeps the literal path.
    if cli_cfg.get("prefer_certified", True):
        from sheeprl_tpu.utils.checkpoint import is_certified, latest_certified

        if not is_certified(ckpt_path):
            certified = latest_certified(os.path.dirname(ckpt_path))
            if certified is not None and os.path.abspath(certified) != ckpt_path:
                warnings.warn(
                    f"checkpoint_path '{ckpt_path}' is not certified; evaluating the certified "
                    f"sibling '{certified}' instead (pass prefer_certified=False to override)"
                )
                ckpt_path = os.path.abspath(certified)
    cfg_path = os.path.join(os.path.dirname(ckpt_path), os.pardir, "config.yaml")
    if not os.path.isfile(cfg_path):
        raise RuntimeError(f"The config file of the checkpoint does not exist: {cfg_path}")
    import yaml

    with open(cfg_path) as f:
        cfg = dotdict(yaml.safe_load(f))
    cfg.checkpoint_path = ckpt_path
    # Evaluation runs single-device / single-env (reference cli.py:383-390)
    cfg.env.num_envs = 1
    cfg.fabric.devices = 1
    cfg.env.capture_video = bool(cli_cfg.get("env.capture_video", cfg.env.get("capture_video", True)))
    if "fabric.accelerator" in cli_cfg:
        cfg.fabric.accelerator = cli_cfg["fabric.accelerator"]
    if "seed" in cli_cfg:
        cfg.seed = cli_cfg["seed"]
    if "float32_matmul_precision" in cli_cfg:
        cfg.float32_matmul_precision = cli_cfg["float32_matmul_precision"]
    check_configs_evaluation(cfg)
    eval_algorithm(cfg)


def registration(overrides: Optional[Sequence[str]] = None) -> None:
    """`sheeprl-registration` entry: register checkpointed models in a model registry.

    Reference: sheeprl/cli.py:408-450 (MLflow-backed). Here the default backend is
    the local filesystem registry (sheeprl_tpu/utils/model_manager.py); the command
    boots entirely from the checkpoint's sidecar config, like evaluation.
    Usage: ``sheeprl-registration checkpoint_path=<ckpt> [model_manager.registry_dir=...]``.
    """
    import yaml

    from sheeprl_tpu.utils.model_manager import register_model_from_checkpoint

    overrides = list(overrides if overrides is not None else sys.argv[1:])
    cli_cfg: Dict[str, Any] = {}
    for ov in overrides:
        key, _, value = ov.partition("=")
        cli_cfg[key.strip()] = yaml.safe_load(value)
    ckpt_path = cli_cfg.pop("checkpoint_path", None)
    if ckpt_path is None:
        raise ConfigError("You must specify checkpoint_path=<path> for model registration")
    ckpt_path = os.path.abspath(ckpt_path)
    cfg_path = os.path.join(os.path.dirname(ckpt_path), os.pardir, "config.yaml")
    if not os.path.isfile(cfg_path):
        raise RuntimeError(f"The config file of the checkpoint does not exist: {cfg_path}")
    with open(cfg_path) as f:
        cfg = dotdict(yaml.safe_load(f))
    for key, value in cli_cfg.items():  # dotted overrides, e.g. model_manager.registry_dir=...
        node = cfg
        parts = key.split(".")
        for p in parts[:-1]:
            node = node.setdefault(p, dotdict({}))
        node[parts[-1]] = value
    cfg.env.num_envs = 1
    cfg.fabric.devices = 1

    _import_algorithms()
    entry = _find_entrypoint(cfg.algo.name)
    if entry is None:
        raise RuntimeError(f"Given the algorithm named '{cfg.algo.name}', no entrypoint has been registered")
    utils = importlib.import_module(f"{entry['module']}.utils")
    log_models_fn = getattr(utils, "log_models_from_checkpoint", None)
    if log_models_fn is None:
        raise RuntimeError(f"The algorithm '{cfg.algo.name}' does not support model registration")

    runtime = Runtime(accelerator=cfg.fabric.get("accelerator", "auto"), devices=1, precision=cfg.fabric.precision)
    seed_everything(cfg.seed)
    from sheeprl_tpu.utils.checkpoint import load_state

    state = load_state(ckpt_path)
    registered = register_model_from_checkpoint(runtime, cfg, state, log_models_fn)
    for name, version in registered.items():
        runtime.print(f"{name}: registered as '{version.name}' v{version.version} at {version.path}")


def serve(overrides: Optional[Sequence[str]] = None) -> None:
    """`sheeprl-serve` entry: batched policy inference with certified hot-reload.

    Two sources, same runtime:

    - ``checkpoint_path=<ckpt>``: boot from the checkpoint's sidecar config,
      preferring the newest CERTIFIED sibling in the same dir (the trainer may
      still be writing there — the hot-reloader then keeps following
      ``latest_certified``). ``prefer_certified=False`` pins the literal path.
    - ``model_name=<registered name>`` (optionally ``model_version=N``): serve
      a registry version directly by name. The registration flow stores each
      version's run config next to its weights, so no checkpoint dir is needed
      (and hot-reload is off: registry versions are immutable).

    Any ``serve.*`` dotted override reaches the config group
    (``serve.queue.admission=shed_oldest`` etc.); ``stats_file=<path>`` writes
    the final ``Serve/*`` snapshot on graceful shutdown.
    """
    import yaml

    from sheeprl_tpu.serve.server import PolicyServer

    overrides = list(overrides if overrides is not None else sys.argv[1:])
    cli_cfg: Dict[str, Any] = {}
    for ov in overrides:
        key, _, value = ov.partition("=")
        cli_cfg[key.strip()] = yaml.safe_load(value)

    model_name = cli_cfg.pop("model_name", None)
    ckpt_path = cli_cfg.pop("checkpoint_path", None)
    stats_file = cli_cfg.pop("stats_file", None)
    prefer_certified = cli_cfg.pop("prefer_certified", True)
    ckpt_dir: Optional[str] = None
    boot_info: Optional[Dict[str, Any]] = None
    if model_name is not None:
        from sheeprl_tpu.utils.model_manager import LocalModelManager, default_registry_dir

        registry_dir = cli_cfg.pop("model_manager.registry_dir", None) or default_registry_dir(None)
        manager = LocalModelManager(None, registry_dir)
        version = cli_cfg.pop("model_version", None)
        if version is None:
            version = manager.get_latest_version(model_name).version
        state = {"agent": manager.load_model(model_name, version)}
        cfg = manager.load_version_config(model_name, version)
        source = f"registry://{model_name}/v{version}"
    elif ckpt_path is not None:
        from sheeprl_tpu.utils.checkpoint import certified_info, is_certified, latest_certified

        ckpt_path = os.path.abspath(ckpt_path)
        ckpt_dir = os.path.dirname(ckpt_path)
        if prefer_certified and not is_certified(ckpt_path):
            certified = latest_certified(ckpt_dir)
            if certified is not None:
                warnings.warn(
                    f"checkpoint_path '{ckpt_path}' is not certified; serving the certified "
                    f"sibling '{certified}' instead (pass prefer_certified=False to override)"
                )
                ckpt_path = os.path.abspath(certified)
        cfg_path = os.path.join(ckpt_dir, os.pardir, "config.yaml")
        if not os.path.isfile(cfg_path):
            raise RuntimeError(f"The config file of the checkpoint does not exist: {cfg_path}")
        with open(cfg_path) as f:
            cfg = dotdict(yaml.safe_load(f))
        state = load_state(ckpt_path)
        source = ckpt_path
        # sidecar identity (crc) lets the hot-reloader skip the artifact that
        # is already serving instead of re-loading it as a new generation
        boot_info = certified_info(ckpt_path)
    else:
        raise ConfigError("You must specify checkpoint_path=<path> or model_name=<name> for serving")

    for key, value in cli_cfg.items():  # dotted overrides, e.g. serve.queue.admission=...
        node = cfg
        parts = key.split(".")
        for p in parts[:-1]:
            node = node.setdefault(p, dotdict({}))
        node[parts[-1]] = value
    cfg.fabric.devices = 1
    seed_everything(cfg.seed)
    _apply_global_flags(cfg, plane="serve")
    server = PolicyServer(cfg, state, source=source, ckpt_dir=ckpt_dir, boot_info=boot_info)
    server.start()
    print(f"serving on {server.host}:{server.port} (source {source})", flush=True)
    server.serve_until_stopped(stats_file=stats_file)


def run(overrides: Optional[Sequence[str]] = None) -> None:
    """Main `sheeprl` entry (reference: sheeprl/cli.py:358-366)."""
    t0 = time.perf_counter()
    overrides = list(overrides if overrides is not None else sys.argv[1:])
    cfg = compose(config_name="config", overrides=overrides)
    cfg = resume_from_checkpoint(cfg)
    check_configs(cfg)
    run_algorithm(cfg)
    if cfg.get("exp", {}) and cfg.get("run_benchmarks", False):
        print(f"Elapsed time: {time.perf_counter() - t0:.3f} s")
