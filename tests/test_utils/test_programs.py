"""Compiled-program observatory (sheeprl_tpu/telemetry/programs.py): row
schema round-trip through a REAL AOT compile, HLO-fingerprint stability, the
diff CLI catching a seeded memory regression and a sharding change, the
warm-step zero-cost proof under ``jax.transfer_guard``, the Prometheus
collision dedupe, and the bench cross-run regression sentinel."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench
from sheeprl_tpu.core import compile as jax_compile
from sheeprl_tpu.core import failpoints
from sheeprl_tpu.telemetry import export as tel_export
from sheeprl_tpu.telemetry import programs as tel_programs
from sheeprl_tpu.telemetry import registry as tel_registry
from sheeprl_tpu.telemetry import trace

pytestmark = pytest.mark.telemetry


@pytest.fixture(autouse=True)
def _clean_observatory():
    trace.disable()
    tel_registry.clear()
    failpoints.reset()
    tel_programs.reset()
    yield
    trace.disable()
    tel_registry.clear()
    failpoints.reset()
    tel_programs.reset()


def _compile_demo(name="obs.demo", n=32, **jit_kwargs):
    gfn = jax_compile.guarded_jit(
        lambda x, y: (x @ y).sum(), name=name, donate_argnums=(0,), **jit_kwargs
    )
    spec = jax.ShapeDtypeStruct((n, n), jnp.float32)
    gfn.aot_compile(spec, spec)
    return gfn


# --------------------------------------------------------------------------- #
# capture: one real compile -> one complete, schema-versioned JSONL row
# --------------------------------------------------------------------------- #


def test_ledger_row_schema_roundtrip(tmp_path):
    path = str(tmp_path / "programs.jsonl")
    tel_programs.configure(path, mirror_env=False)
    trace.configure(plane="test", trace_id="progrows")
    _compile_demo()

    rows = tel_programs.read_ledger(path)
    assert len(rows) == 1
    row = rows[0]
    assert row["schema"] == tel_programs.SCHEMA_VERSION
    assert row["name"] == "obs.demo"
    # the acceptance bar: fingerprint, FLOPs, HBM breakdown and shardings all
    # non-null for a program compiled on this (CPU) backend
    assert isinstance(row["fingerprint"], str) and len(row["fingerprint"]) == 24
    assert row["flops"] > 0
    assert row["compile_seconds"] > 0
    mem = row["memory"]
    for key in (
        "argument_bytes",
        "output_bytes",
        "temp_bytes",
        "generated_code_bytes",
        "alias_bytes",
        "peak_bytes",
    ):
        assert key in mem, f"memory breakdown missing {key}"
    assert row["input_shardings"] and row["output_shardings"]
    assert row["donation"] == {"argnums": [0]}
    assert row["trace_id"] == "progrows"
    assert row["backend"] == "cpu"
    json.dumps(row)  # the ledger contract: plain-JSON rows

    # the in-memory registry feeds the metrics fabric even without a path
    g = tel_programs.gauges()
    assert g["Programs/recorded"] == 1.0
    assert g["Program/obs.demo/peak_hbm_bytes"] == mem["peak_bytes"]
    assert g["Program/obs.demo/flops"] == row["flops"]


def test_fingerprint_stable_across_recompiles_and_churns_on_change():
    def f(x, y):
        return (x @ y).sum()

    spec = jax.ShapeDtypeStruct((16, 16), jnp.float32)
    fps = []
    for _ in range(2):
        tel_programs.reset()
        jax_compile.guarded_jit(f, name="obs.fp").aot_compile(spec, spec)
        fps.append(tel_programs.snapshot()[0]["fingerprint"])
    assert fps[0] == fps[1], "identical program must hash identically across compiles"

    tel_programs.reset()
    wide = jax.ShapeDtypeStruct((32, 16), jnp.float32)
    jax_compile.guarded_jit(f, name="obs.fp").aot_compile(wide, spec)
    assert tel_programs.snapshot()[0]["fingerprint"] != fps[0], "shape change must churn the hash"


def test_mesh_sharded_program_records_named_shardings():
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    # conftest forces 8 host-platform devices; a 2-device mesh is always there
    mesh = Mesh(np.array(jax.devices()[:2]), ("d",))
    sharded = NamedSharding(mesh, PartitionSpec("d"))
    spec = jax.ShapeDtypeStruct((16, 8), jnp.float32)

    jax_compile.guarded_jit(lambda x: x * 2.0, name="obs.mesh.repl").aot_compile(spec)
    jax_compile.guarded_jit(
        lambda x: x * 2.0, name="obs.mesh.shard", in_shardings=(sharded,), out_shardings=sharded
    ).aot_compile(spec)

    rows = {r["name"]: r for r in tel_programs.snapshot()}
    sh = rows["obs.mesh.shard"]["input_shardings"]
    assert sh and any("NamedSharding" in s for s in sh)
    assert sh != rows["obs.mesh.repl"]["input_shardings"]
    assert rows["obs.mesh.shard"]["num_devices"] >= 2


def test_record_failpoint_reaches_the_chaos_drill_and_only_it():
    failpoints.configure("telemetry.program_record:raise")
    with pytest.raises(failpoints.FailpointError):
        _compile_demo(name="obs.drill")
    failpoints.reset()
    # any OTHER capture failure degrades to a skipped row, never a failed compile
    _compile_demo(name="obs.ok")
    assert tel_programs.stats()["rows_recorded"] == 1


def test_warm_step_never_touches_the_observatory(monkeypatch):
    """Recording happens at compile time ONLY: a warm call does zero ledger
    work and zero host transfers (the steady-state cost of the observatory)."""
    gfn = jax_compile.guarded_jit(lambda x: x + 1.0, name="obs.warm")
    spec = jax.ShapeDtypeStruct((8,), jnp.float32)
    gfn.aot_compile(spec)
    x = jax.device_put(jnp.zeros((8,), jnp.float32))
    x = gfn(x)  # first dispatch through the AOT executable
    jax.block_until_ready(x)
    assert tel_programs.stats()["rows_recorded"] == 1

    def boom(*a, **k):
        raise AssertionError("programs.record() reached from a warm step")

    monkeypatch.setattr(tel_programs, "record", boom)
    with jax.transfer_guard("disallow"):
        x = gfn(x)
        jax.block_until_ready(x)  # fence only — not a transfer
    assert tel_programs.stats()["rows_recorded"] == 1


def test_env_var_wins_over_train_loop_default(tmp_path, monkeypatch):
    pinned = str(tmp_path / "parent.jsonl")
    monkeypatch.setenv(tel_programs.ENV_VAR, pinned)
    tel_programs.configure_from_env()
    # the per-run default a train loop installs must not sever the parent pin
    tel_programs.configure_default(str(tmp_path / "child.jsonl"))
    assert tel_programs.ledger_path() == pinned


# --------------------------------------------------------------------------- #
# diff CLI: seeded +10% temp-HBM and a sharding flip must be flagged (rc 1)
# --------------------------------------------------------------------------- #


def _doctored_copy(rows, *, temp_factor=1.10, flip_sharding=True):
    out = []
    for row in rows:
        row = json.loads(json.dumps(row))  # deep copy
        mem = row.get("memory") or {}
        if "temp_bytes" in mem:
            delta = mem["temp_bytes"] * (temp_factor - 1.0) or 4096.0 * (temp_factor - 1.0) * 10
            mem["temp_bytes"] += delta
            mem["peak_bytes"] = mem.get("peak_bytes", 0.0) + delta
        if flip_sharding and row.get("input_shardings"):
            row["input_shardings"] = ["NamedSharding(resharded)"] + row["input_shardings"][1:]
        out.append(row)
    return out


def test_diff_cli_flags_seeded_memory_and_sharding_regressions(tmp_path, capsys):
    ledger_a = str(tmp_path / "a" / "programs.jsonl")
    tel_programs.configure(ledger_a, mirror_env=False)
    _compile_demo(name="obs.diff", n=64)
    rows = tel_programs.read_ledger(ledger_a)
    assert rows and rows[0]["memory"]["temp_bytes"] >= 0

    ledger_b = str(tmp_path / "b" / "programs.jsonl")
    os.makedirs(os.path.dirname(ledger_b))
    with open(ledger_b, "w") as f:
        for row in _doctored_copy(rows):
            f.write(json.dumps(row) + "\n")

    rc = tel_programs.main(["diff", ledger_a, ledger_b, "--json"])
    report = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert any(
        d["field"] == "temp_bytes" and d["regression"] for d in report["memory_deltas"]
    ) or any(d["field"] == "peak_bytes" and d["regression"] for d in report["memory_deltas"])
    assert any(c["io"] == "input_shardings" for c in report["sharding_changes"])
    assert report["regressions"]

    # identical ledgers: rc 0 and an explicitly clean text report
    rc = tel_programs.main(["diff", ledger_a, ledger_a])
    out = capsys.readouterr().out
    assert rc == 0 and "no regressions flagged" in out


def test_diff_resolves_run_directories_and_skips_torn_rows(tmp_path, capsys):
    run = tmp_path / "run" / "telemetry"
    run.mkdir(parents=True)
    row = {"schema": 1, "name": "p", "fingerprint": "x", "memory": {"temp_bytes": 10.0}}
    (run / "programs.jsonl").write_text(
        json.dumps(row) + "\n" + "{torn json\n" + json.dumps({**row, "schema": 99}) + "\n"
    )
    rows = tel_programs.read_ledger(str(run / "programs.jsonl"))
    assert len(rows) == 1, "corrupt and future-schema rows must be skipped"
    rc = tel_programs.main(["diff", str(tmp_path / "run"), str(tmp_path / "run")])
    capsys.readouterr()
    assert rc == 0


# --------------------------------------------------------------------------- #
# satellite: Prometheus name-collision dedupe in the exporter
# --------------------------------------------------------------------------- #


def test_prometheus_collision_dedupe_is_deterministic_and_counted():
    # "Programs/recorded" and "Programs.recorded" both sanitize to
    # sheeprl_programs_recorded — invalid exposition if both are emitted
    metrics = {"Programs/recorded": 1.0, "Programs.recorded": 2.0, "Other/ok": 3.0}
    text = tel_export.to_prometheus(metrics)
    body = [ln for ln in text.splitlines() if ln.startswith("sheeprl_programs_recorded")]
    assert body == ["sheeprl_programs_recorded 2"], body  # sorted order: '.' < '/'
    assert "sheeprl_export_series_dropped 1" in text
    assert "sheeprl_other_ok 3" in text
    # no collision -> no dropped series at all
    assert "export_series_dropped" not in tel_export.to_prometheus({"Other/ok": 3.0})


def test_registry_default_providers_include_programs():
    tel_registry.register_default_providers()
    _compile_demo(name="obs.fabric")
    merged = tel_registry.collect()
    assert merged.get("Programs/recorded") == 1.0
    assert "Program/obs.fabric/flops" in merged


# --------------------------------------------------------------------------- #
# satellite: fused-vs-split FLOP/MFU parity on the CartPole config
# --------------------------------------------------------------------------- #


def test_fused_and_split_flops_parity_on_cartpole(monkeypatch):
    """The fused whole-iteration program must account for the same work as
    collect + train compiled apart (cost_analysis FLOPs within tolerance —
    fusion changes scheduling, not the model math), and both paths' MFU
    numerators (``last_step_flops``) must equal their ledger rows."""
    import gymnasium as gym

    from sheeprl_tpu.algos.ppo.agent import build_agent
    from sheeprl_tpu.algos.ppo.ppo import make_train_fn, make_update_impl
    from sheeprl_tpu.config import instantiate, load_config
    from sheeprl_tpu.core.runtime import build_runtime
    from sheeprl_tpu.envs import ingraph as ig
    from sheeprl_tpu.telemetry import device as tel_device
    from sheeprl_tpu.utils.optim import with_clipping
    from sheeprl_tpu.utils.utils import PlayerParamsSync

    n_envs, t_steps = 16, 8
    n_data = n_envs * t_steps
    cfg = load_config(
        overrides=[
            "exp=ppo",
            "env=jax_cartpole",
            f"env.num_envs={n_envs}",
            f"algo.rollout_steps={t_steps}",
            f"algo.per_rank_batch_size={n_data}",
            "algo.update_epochs=1",
            "algo.mlp_keys.encoder=[state]",
            "algo.cnn_keys.encoder=[]",
            "seed=7",
        ]
    )
    runtime = build_runtime(cfg.fabric)
    venv = ig.make_vector_env(cfg, n_envs, 7, device=runtime.device)
    space = venv.single_action_space
    assert isinstance(space, gym.spaces.Discrete)
    agent, params, player = build_agent(
        runtime, (int(space.n),), False, cfg, venv.single_observation_space, None
    )
    player.params = jax.device_put(player.params, runtime.device)
    venv.reset(seed=7)
    tx = with_clipping(instantiate(dict(cfg.algo.optimizer))(), cfg.algo.max_grad_norm)
    opt_state = tx.init(params)
    params_sync = PlayerParamsSync(player.params)
    scalar = jax.ShapeDtypeStruct((), jnp.float32)

    # split path: rollout and train compiled apart
    split_col = ig.InGraphRolloutCollector(
        venv, player, rollout_steps=t_steps, gamma=float(cfg.algo.gamma), name="parity_split"
    )
    split_col.collect_fn.aot_compile(*split_col.warmup_specs())
    data_s, nv_s = split_col.output_specs()
    train_fn = make_train_fn(agent, tx, cfg, runtime, n_data, ["state"], [], params_sync)
    train_fn.aot_compile(
        jax_compile.specs_of(params),
        jax_compile.specs_of(opt_state),
        data_s,
        nv_s,
        jax_compile.spec_like(jax.random.PRNGKey(0)),
        scalar,
        scalar,
        scalar,
    )

    # fused path: its own collector instance (a shared one would leak tracers)
    fused_col = ig.InGraphRolloutCollector(
        venv, player, rollout_steps=t_steps, gamma=float(cfg.algo.gamma), name="parity_fused"
    )
    update_impl = make_update_impl(agent, tx, cfg, runtime, n_data, ["state"], [], params_sync)
    trainer = ig.FusedInGraphTrainer(fused_col, update_impl, n_extras=3, name="parity_fused")
    extras = (jnp.float32(cfg.algo.clip_coef), jnp.float32(cfg.algo.ent_coef), jnp.float32(1.0))
    trainer.step_fn.aot_compile(
        *trainer.warmup_specs(params, opt_state, jax.random.PRNGKey(5), *extras)
    )

    rows = {r["name"]: r for r in tel_programs.snapshot()}
    fused = rows["parity_fused.ingraph_train"]["flops"]
    split = rows["parity_split.ingraph_collect"]["flops"] + rows["ppo.train"]["flops"]
    assert fused > 0 and split > 0
    assert abs(fused - split) / split < 0.25, (fused, split)

    # the MFU numerators are exactly the ledger FLOPs on both paths
    assert trainer.step_fn.last_step_flops == fused
    assert train_fn.last_step_flops == rows["ppo.train"]["flops"]

    # identical FLOPs + time => identical MFU math on both paths (CPU has no
    # peak-FLOPs table entry, so pin one)
    monkeypatch.setattr(tel_device, "chip_peak_flops", lambda device=None: 1.0e12)
    assert tel_device.mfu(fused, 0.01, runtime.device) == pytest.approx(fused / 0.01 / 1.0e12)
    assert tel_device.mfu(rows["ppo.train"]["flops"], 0.01, runtime.device) == pytest.approx(
        rows["ppo.train"]["flops"] / 0.01 / 1.0e12
    )
    venv.close()


# --------------------------------------------------------------------------- #
# bench cross-run regression sentinel
# --------------------------------------------------------------------------- #


def _write_bench_ledger(path, rows):
    with open(path, "w") as f:
        for row in rows:
            f.write(json.dumps(row) + "\n")


_BASE_ROUND = {
    "status": "ok",
    "env_steps_per_sec": 1000.0,
    "infer_p99_ms": 10.0,
    "device_hbm_peak_bytes": 1.0e9,
    "mfu": 0.30,
}


def test_sentinel_passes_on_a_clean_ledger(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    _write_bench_ledger(path, [dict(_BASE_ROUND, run_id=f"r{i}") for i in range(4)])
    report, rc = bench.check_regressions(path)
    assert rc == 0 and report["status"] == "ok"
    assert report["checked"] >= 3
    assert report["Regress/env_steps_per_sec"]["breach"] is False


def test_sentinel_fails_on_a_doctored_round(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    rows = [dict(_BASE_ROUND, run_id=f"r{i}") for i in range(3)]
    rows.append(
        dict(_BASE_ROUND, run_id="bad", env_steps_per_sec=500.0, infer_p99_ms=40.0)
    )
    report, rc = bench.check_regressions(path)
    # ledger not written yet: missing file is a skip, not a crash
    assert rc == 0 and report["status"] == "skipped"
    _write_bench_ledger(path, rows)
    report, rc = bench.check_regressions(path)
    assert rc == 4 and report["status"] == "regressed"
    assert "env_steps_per_sec" in report["regressions"]
    assert "infer_p99_ms" in report["regressions"]
    assert report["Regress/env_steps_per_sec"]["direction"] == "higher"
    assert report["Regress/device_hbm_peak_bytes"]["breach"] is False

    # per-metric threshold override: a 50%-drop allowance silences the SPS breach
    report, rc = bench.check_regressions(path, {"env_steps_per_sec": 0.6, "infer_p99_ms": 5.0})
    assert rc == 0, report["regressions"]


def test_sentinel_compares_only_same_status_rounds(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    rows = [dict(_BASE_ROUND, run_id="old0", status="skipped", env_steps_per_sec=50.0)]
    rows.append(dict(_BASE_ROUND, run_id="ok0"))
    _write_bench_ledger(path, rows)
    report, rc = bench.check_regressions(path)
    # an ok round must never be judged against rows of another status
    assert rc == 0 and report["status"] == "skipped"


def test_bench_ledger_append_roundtrip_and_failpoint_drop(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    bench._append_ledger({"status": "ok", "value": 1}, path)
    failpoints.configure("bench.ledger_append:drop")
    bench._append_ledger({"status": "ok", "value": 2}, path)
    failpoints.reset()
    rows = bench._read_bench_ledger(path)
    assert [r["value"] for r in rows] == [1], "dropped append must not reach the file"


def test_parse_thresholds():
    assert bench._parse_thresholds(["a=0.5", "b_p99_ms=1.0"]) == {"a": 0.5, "b_p99_ms": 1.0}
    with pytest.raises(SystemExit):
        bench._parse_thresholds(["nope"])
