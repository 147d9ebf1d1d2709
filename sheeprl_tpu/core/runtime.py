"""TPU runtime context: device mesh, precision policy, sharding helpers, seeding.

This is the TPU-native replacement for Lightning Fabric (reference L0,
sheeprl/configs/fabric/default.yaml + sheeprl/cli.py:199). Design differences, on purpose:

- Single-controller SPMD: one Python process drives all local devices through a
  ``jax.sharding.Mesh``; data parallelism is expressed by sharding the batch on the
  ``data`` mesh axis and keeping params replicated — XLA inserts the gradient
  all-reduce over ICI (no DDP wrappers, no NCCL process groups).
- Multi-host: ``jax.distributed.initialize`` (config ``fabric.multihost``) extends the
  same mesh over DCN; ``global_rank``/``world_size`` then reflect processes, while the
  mesh spans all global devices.
- Precision: a policy pair (param_dtype, compute_dtype). ``bf16-mixed`` = fp32 params +
  bf16 compute (matches the stability recipe of the reference's ``bf16-true`` runs with
  dtype-preserving LayerNorms, sheeprl/models/models.py:507-525).
"""

from __future__ import annotations

import os
import random
from contextlib import nullcontext
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax._src import distributed as _jax_distributed
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sheeprl_tpu.core.compile import setup_phase

_PRECISIONS = {
    "32-true": (jnp.float32, jnp.float32),
    "32": (jnp.float32, jnp.float32),
    "bf16-mixed": (jnp.float32, jnp.bfloat16),
    "bf16-true": (jnp.bfloat16, jnp.bfloat16),
    "16-mixed": (jnp.float32, jnp.float16),
}


def _distributed_initialized() -> bool:
    """Whether jax.distributed.initialize() has already run in this process.

    Must stay backend-free: ``jax.process_count()`` would initialize the backend
    and break a subsequent ``initialize()``. The private module is imported
    plainly at the top of this file, so a jax that moves it is an ImportError,
    not a silently skipped check."""
    return _jax_distributed.global_state.client is not None


def enable_cpu_collectives() -> None:
    """Switch the CPU backend's cross-process collectives to gloo.

    The default CPU client refuses multi-process computations outright
    ("Multiprocess computations aren't implemented on the CPU backend"), which
    kept every multihost code path untestable off-pod. Must run BEFORE the
    backend initializes; a no-op on TPU/GPU platforms."""
    plat = os.environ.get("JAX_PLATFORMS") or str(jax.config.jax_platforms or "")
    if "cpu" not in plat.split(","):
        return
    jax.config.update("jax_cpu_collectives_implementation", "gloo")


def seed_everything(seed: int) -> int:
    """Seed python/numpy; JAX randomness is explicit via PRNG keys derived from the seed.

    Reference: ``fabric.seed_everything`` via the ``reproducible`` wrapper
    (sheeprl/cli.py:187-197).
    """
    random.seed(seed)
    np.random.seed(seed % (2**32))
    os.environ["PYTHONHASHSEED"] = str(seed)
    return seed


def _fsdp_partition_spec(name: str, shape: Sequence[int], n: int) -> P:
    """Explicit FSDP spec for one leaf (see Runtime.shard_model_params's table).

    ``name`` is the lowercase tree path (flax module / optax state path), so the
    rules key on the flax conventions: ``kernel`` for dense/conv weights (output
    features/channels last), ``bias``/``scale`` for the small vectors.
    """
    if not shape:
        return P()
    last = len(shape) - 1
    if "kernel" in name and len(shape) >= 2:
        if shape[last] % n == 0 and shape[last] >= n:
            spec = [None] * len(shape)
            spec[last] = "data"
            return P(*spec)
        # indivisible output dim (e.g. small action/value heads): replicate rather
        # than fall through to a contraction-dim shard, which would trade the tiny
        # memory win for a per-layer activation all-gather
        return P()
    if "bias" in name or "scale" in name:
        return P()
    divisible = [(d, s) for d, s in enumerate(shape) if s % n == 0 and s >= n]
    if not divisible:
        return P()
    dim = max(divisible, key=lambda t: t[1])[0]
    spec = [None] * len(shape)
    spec[dim] = "data"
    return P(*spec)


@dataclass
class Runtime:
    """Accelerator + distributed context handed to every algorithm entrypoint."""

    accelerator: str = "auto"
    devices: Any = "auto"
    strategy: str = "auto"
    precision: str = "32-true"
    mesh_axes: Sequence[str] = ("data",)
    callbacks: Sequence[Any] = field(default_factory=list)
    multihost: bool = False
    player_on_host: bool = True
    # manual coordinator wiring (fabric.coordinator_address etc.); None = the
    # launcher's cluster auto-detection. multihost_timeout_s bounds the wait for
    # an absent/unreachable coordinator instead of jax's 300 s default.
    coordinator_address: Optional[str] = None
    num_processes: Optional[int] = None
    process_id: Optional[int] = None
    multihost_timeout_s: Optional[float] = None
    # XLA scheduling profile (fabric.xla_profile; parallel/overlap.py): applied
    # FIRST in __post_init__, before anything here can initialize the backend
    # and freeze XLA_FLAGS.
    xla_profile: Optional[str] = None

    def __post_init__(self):
        if self.xla_profile:
            from sheeprl_tpu.parallel import overlap

            overlap.apply_xla_profile(self.xla_profile)
        if self.multihost and not _distributed_initialized():
            # The guard must NOT probe jax.process_count(): that initializes the local
            # backend, after which jax.distributed.initialize() can no longer run.
            # Fail loudly: silently proceeding single-host after a botched pod config
            # wastes the whole allocation (reference Fabric raises on bad cluster env too).
            enable_cpu_collectives()
            kwargs: Dict[str, Any] = {}
            if self.coordinator_address is not None:
                kwargs.update(
                    coordinator_address=self.coordinator_address,
                    num_processes=self.num_processes,
                    process_id=self.process_id,
                )
            if self.multihost_timeout_s is not None:
                kwargs["initialization_timeout"] = int(self.multihost_timeout_s)
            try:
                jax.distributed.initialize(**kwargs)
            except Exception as e:
                if "already" in str(e).lower():  # initialized by a launcher/earlier Runtime
                    pass
                else:
                    raise RuntimeError(
                        "fabric.multihost=True but jax.distributed.initialize() failed "
                        "(coordinator absent/unreachable?). Check the coordinator address / "
                        "JAX_COORDINATOR_ADDRESS and pod env, and make sure the Runtime is "
                        "constructed before any JAX computation."
                    ) from e
            print(
                f"[sheeprl_tpu] multihost initialized: process "
                f"{jax.process_index()}/{jax.process_count()}, "
                f"{jax.local_device_count()} local / {jax.device_count()} global devices"
            )
        if self.multihost:
            self._validate_homogeneous_devices()
        # "auto" takes JAX's default backend. A NAMED accelerator is asked for by
        # name and its absence raises: fabric.accelerator=tpu on a host with no
        # TPU must not train on the CPU without a word.
        platform = None if self.accelerator == "auto" else {"cuda": "gpu"}.get(
            self.accelerator, self.accelerator
        )
        try:
            all_devices = jax.devices(platform)
        except RuntimeError as e:
            raise RuntimeError(
                f"fabric.accelerator={self.accelerator!r} but JAX has no '{platform}' backend "
                f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}, default backend "
                f"'{jax.default_backend()}'): {e}"
            ) from e
        n = self.devices
        if n in ("auto", None, -1, "-1"):
            n = len(all_devices)
        n = int(n)
        if n > len(all_devices):
            raise ValueError(f"Requested {n} devices but only {len(all_devices)} available: {all_devices}")
        self._devices = all_devices[:n]
        axes = tuple(self.mesh_axes)
        if len(axes) == 1:
            shape = (n,)
        else:
            # trailing axes get size 1 unless configured via `devices` being a list
            shape = (n,) + (1,) * (len(axes) - 1)
        self.mesh = Mesh(np.asarray(self._devices).reshape(shape), axes)
        if platform is not None and self._devices[0].platform != jax.devices()[0].platform:
            # An explicit non-default accelerator (e.g. fabric.accelerator=cpu on a
            # TPU host for tiny latency-bound workloads): uncommitted ops
            # (jnp.asarray, jax.random.*) must land on the chosen backend too, or
            # every loop iteration silently bounces through the default device.
            jax.config.update("jax_default_device", self._devices[0])
        else:
            # restore the platform default so a cpu-pinned Runtime earlier in this
            # process (tests, exploration->finetuning chains) cannot leak its
            # default-device override into this run
            jax.config.update("jax_default_device", None)
        if self.precision not in _PRECISIONS:
            raise ValueError(f"Unknown precision '{self.precision}'. Choose from {list(_PRECISIONS)}")
        self.param_dtype, self.compute_dtype = _PRECISIONS[self.precision]

    # ----- topology ------------------------------------------------------------------
    @property
    def world_size(self) -> int:
        """Number of data-parallel shards (devices in the mesh)."""
        return int(np.prod(self.mesh.devices.shape))

    @property
    def global_rank(self) -> int:
        return jax.process_index()

    @property
    def node_rank(self) -> int:
        return jax.process_index()

    @property
    def is_global_zero(self) -> bool:
        return jax.process_index() == 0

    @property
    def device(self):
        return self._devices[0]

    @property
    def host_device(self):
        """THIS process's host CPU backend device (in a multi-process world
        ``jax.devices`` leads with process 0's devices, which are
        non-addressable here). ``JAX_PLATFORMS`` must leave the CPU backend in
        (unset, or e.g. ``tpu,cpu``): a bare ``JAX_PLATFORMS=tpu`` removes it,
        and the host player must not then move onto the chip unannounced."""
        try:
            return jax.local_devices(backend="cpu")[0]
        except RuntimeError as e:
            raise RuntimeError(
                "No CPU backend for the host player/buffers "
                f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}). Leave JAX_PLATFORMS unset "
                "or include cpu (JAX_PLATFORMS=tpu,cpu); to run the player on the accelerator "
                "on purpose set fabric.player_on_host=False."
            ) from e

    @property
    def player_device(self):
        """Where the rollout policy runs.

        Every per-env-step policy call on the accelerator is a synchronous
        host<->device round trip, so by default the player runs on the host CPU
        backend and only the train step uses the accelerator
        (``fabric.player_on_host=False`` opts back into on-accelerator rollouts,
        e.g. for big CNN policies).
        """
        if not self.player_on_host:
            return self._devices[0]
        return self.host_device

    def to_player(self, tree):
        """Move a pytree to the player device (committed), e.g. post-update params.

        Values replicated over a cross-process mesh are not fully addressable;
        this process's own replica is read first, making the put a local D2D
        transfer (the cross-host decoupled parameter-refresh path). When the
        player chip belongs to ANOTHER process, the put lands on this process's
        host device instead — only the player process drives envs, so the
        shadow copy is inert, but agent construction stays symmetric across
        the world (every process calls build_agent).
        """
        dev = self.player_device
        if getattr(dev, "process_index", jax.process_index()) != jax.process_index():
            dev = self.host_device

        def put(x):
            if isinstance(x, jax.Array) and not x.is_fully_addressable:
                if not x.sharding.is_fully_replicated:
                    # addressable_data(0) would be ONE shard, silently truncating
                    # the leaf (cross-process FSDP params have no local full copy)
                    raise ValueError(
                        "Cannot ship cross-process SHARDED params to the player; "
                        "keep the player copy replicated (DDP placement) or gather first"
                    )
                x = x.addressable_data(0)
            return jax.device_put(x, dev)

        return jax.tree_util.tree_map(put, tree)

    # ----- sharding ------------------------------------------------------------------
    @property
    def data_sharding(self) -> NamedSharding:
        """Batch-dim sharding over the 'data' mesh axis."""
        return NamedSharding(self.mesh, P("data"))

    @property
    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def shard_batch(self, tree):
        """Move a host pytree to device, sharded on the leading (batch) axis."""
        sh = self.data_sharding
        return jax.tree_util.tree_map(lambda x: jax.device_put(x, sh), tree)

    def replicate(self, tree):
        """Move a pytree to device, replicated across the mesh."""
        sh = self.replicated
        return jax.tree_util.tree_map(lambda x: jax.device_put(x, sh), tree)

    def shard_model_params(self, tree):
        """FSDP-style placement over the ``data`` axis, by explicit per-leaf rules.

        With the batch sharded on the same axis, XLA's SPMD partitioner inserts
        the all-gathers (forward/backward) and keeps the optimizer update fully
        sharded — the in-graph equivalent of the reference's sharded-DDP/FSDP
        Fabric strategies, and the standard JAX recipe for fitting models larger
        than one chip's HBM. Optimizer state placed with the same function gets
        identical shardings (optax state trees embed the param-tree paths).

        Partition-spec table (leaf path -> spec; W = data-axis size):

        | leaf                                             | spec            |
        |--------------------------------------------------|-----------------|
        | ``*kernel`` ``[in, out]`` dense (incl. the GRU   | shard ``out``   |
        |   gate kernels) and ``[.., cin, cout]`` convs    | (last dim)      |
        | ``*bias`` / ``*scale`` (LayerNorm) / scalars     | replicate       |
        | anything else with a W-divisible dim             | largest such dim|
        | indivisible leaves                               | replicate       |

        Sharding a kernel's OUTPUT dim keeps every contraction local: the
        forward all-gathers weights (ZeRO-3 style) instead of activations, and
        the previous largest-divisible-dim heuristic could pick a contraction
        dim and force a per-layer activation all-gather instead.
        """
        n = int(self.mesh.shape["data"])

        def place(path, x):
            x = jnp.asarray(x) if not hasattr(x, "shape") else x
            name = jax.tree_util.keystr(path).lower()
            shape = tuple(getattr(x, "shape", ()))
            spec = _fsdp_partition_spec(name, shape, n)
            return jax.device_put(x, NamedSharding(self.mesh, spec))

        return jax.tree_util.tree_map_with_path(place, tree)

    def place_params(self, tree):
        """Param/opt-state placement per ``fabric.strategy``: ``fsdp`` shards over
        the mesh, anything else replicates (the DDP default)."""
        if str(self.strategy).lower() == "fsdp":
            return self.shard_model_params(tree)
        return self.replicate(tree)

    def local_batch_slice(self, global_batch: int) -> int:
        if global_batch % self.world_size != 0:
            raise ValueError(f"Global batch {global_batch} not divisible by world size {self.world_size}")
        return global_batch // self.world_size

    # ----- precision -----------------------------------------------------------------
    def cast_compute(self, tree):
        def cast(x):
            if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating):
                return x.astype(self.compute_dtype)
            return x

        return jax.tree_util.tree_map(cast, tree)

    # ----- misc Fabric-parity surface ------------------------------------------------
    def print(self, *args, **kwargs):
        if self.is_global_zero:
            print(*args, **kwargs)

    def call(self, hook_name: str, **kwargs):
        """Invoke callbacks (reference: fabric.call -> CheckpointCallback)."""
        for cb in self.callbacks:
            fn = getattr(cb, hook_name, None)
            if fn is not None:
                fn(runtime=self, **kwargs)

    def barrier(self):
        # Single-controller: nothing to synchronize on host. Multi-controller: a
        # HOST barrier over the coordinator's native barrier service (portable —
        # works wherever the world booted, including the CPU backend), falling
        # back to a device collective only when the KV client is unavailable.
        if jax.process_count() > 1:  # pragma: no cover - exercised by test_multihost children
            from sheeprl_tpu.parallel import control

            if control.host_barrier():
                return
            from jax.experimental import multihost_utils

            multihost_utils.sync_global_devices("sheeprl_tpu_barrier")

    def _validate_homogeneous_devices(self) -> None:
        """Fail fast on heterogeneous per-process device counts.

        DP meshes assume equal per-rank shards (the reference's DDP makes the same
        assumption per node); a pod booted with uneven visible devices would
        otherwise fail much later with an opaque sharding error — or worse, train
        with silently skewed per-rank batches. Exchanged through the coordinator's
        KV store, NOT a device collective: the whole point is that the device
        config may be broken.
        """
        if jax.process_count() <= 1:
            return
        client = _jax_distributed.global_state.client
        if client is None:
            raise RuntimeError("fabric.multihost=True but jax.distributed has no client")
        me = jax.process_index()
        # allow_overwrite: a second Runtime in the same process (launcher case,
        # exploration->finetuning chains) re-validates against the same keys
        client.key_value_set(
            f"sheeprl_tpu/local_devices/{me}", str(jax.local_device_count()), allow_overwrite=True
        )
        counts = {
            p: int(client.blocking_key_value_get(f"sheeprl_tpu/local_devices/{p}", 30_000))
            for p in range(jax.process_count())
        }
        if len(set(counts.values())) > 1:
            raise RuntimeError(
                f"Heterogeneous local device counts across processes: {counts}. "
                "Data-parallel meshes need the same per-process device count — check "
                "each host's visible accelerators / XLA flags."
            )

    def seed_everything(self, seed: int) -> int:
        return seed_everything(seed)


@setup_phase("runtime")
def build_runtime(cfg_fabric: Dict[str, Any], extra_callbacks: Optional[Sequence[Any]] = None) -> Runtime:
    """Instantiate the Runtime from the ``fabric:`` config group."""
    callbacks = []
    for cb_spec in cfg_fabric.get("callbacks", []) or []:
        if isinstance(cb_spec, dict) and "_target_" in cb_spec:
            from sheeprl_tpu.config import instantiate

            callbacks.append(instantiate(cb_spec))
        else:
            callbacks.append(cb_spec)
    callbacks.extend(extra_callbacks or [])
    return Runtime(
        accelerator=cfg_fabric.get("accelerator", "auto"),
        devices=cfg_fabric.get("devices", "auto"),
        strategy=cfg_fabric.get("strategy", "auto"),
        precision=cfg_fabric.get("precision", "32-true"),
        callbacks=callbacks,
        multihost=bool(cfg_fabric.get("multihost", False)),
        player_on_host=bool(cfg_fabric.get("player_on_host", True)),
        coordinator_address=cfg_fabric.get("coordinator_address"),
        num_processes=cfg_fabric.get("num_processes"),
        process_id=cfg_fabric.get("process_id"),
        multihost_timeout_s=cfg_fabric.get("multihost_timeout_s"),
        xla_profile=cfg_fabric.get("xla_profile"),
    )


def get_single_device_runtime(runtime: Runtime) -> Runtime:
    """A 1-device twin of ``runtime`` for player/eval models.

    Reference: ``get_single_device_fabric`` (sheeprl/utils/fabric.py:8-35).
    """
    return Runtime(
        accelerator=runtime.accelerator,
        devices=1,
        strategy="auto",
        precision=runtime.precision,
        callbacks=list(runtime.callbacks),
        multihost=runtime.multihost,
        player_on_host=runtime.player_on_host,
    )
