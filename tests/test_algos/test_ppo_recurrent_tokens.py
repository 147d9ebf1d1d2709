"""``ppo_recurrent`` with a language-model policy: the experiment through the real CLI at
tiny sizes, the seam's error for sequences that do not start at resets, and the LSTM path
through the seam, bitwise what it was before the seam existed."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from sheeprl_tpu.algos.ppo.loss import policy_loss
from sheeprl_tpu.algos.ppo.utils import normalize_obs
from sheeprl_tpu.algos.ppo_recurrent import ppo_recurrent
from sheeprl_tpu.algos.ppo_recurrent.agent import build_agent, evaluate_actions
from sheeprl_tpu.cli import run
from sheeprl_tpu.config import compose, instantiate
from sheeprl_tpu.core import compile as jax_compile
from sheeprl_tpu.core.runtime import build_runtime
from sheeprl_tpu.utils.optim import with_clipping

TINY = [
    "exp=ppo_recurrent_lfm2_tokens",
    "algo.lm.hidden_size=32", "algo.lm.intermediate_size=48", "algo.lm.moe_intermediate_size=24",
    "algo.lm.num_attention_heads=4", "algo.lm.num_key_value_heads=2", "algo.lm.head_dim=8",
    "algo.lm.num_experts=8", "algo.lm.num_experts_per_tok=2", "algo.lm.experts_held=4", "algo.lm.vocab_held=64",
    "algo.lm.query_block=8", "algo.lm.head_chunk=8",
    "env.wrapper.prompt_tokens=4", "env.wrapper.sampled_tokens=12", "algo.rollout_steps=16",
    "fabric.precision=32-true", "fabric.player_on_host=True", "fabric.devices=1",
    "metric.log_level=0", "checkpoint.save_last=False", "algo.run_test=False",
]


@pytest.mark.parametrize("player_on_host", [True, False])
def test_the_experiment_runs_through_the_cli_with_no_retrace(tmp_path, monkeypatch, player_on_host):
    """Three updates of ``exp=ppo_recurrent_lfm2_tokens`` (two envs, 16-step episodes, the cut's
    five layers at width 32): rollout through the carried state, whole episodes as training
    sequences, the train call; and the acting, value and training programs each traced once, with
    the player beside the learner (the experiment's placement: it acts with the learner's own
    arrays) as on the host (my chip run, PR 29, met two retraces of the act program there)."""
    monkeypatch.chdir(tmp_path)
    run(overrides=TINY + ["algo.total_steps=96", f"fabric.player_on_host={player_on_host}"])
    train, act = jax_compile.find("ppo_recurrent.train"), jax_compile.find("ppo_recurrent.act_packed")
    values, gae = jax_compile.find("ppo_recurrent.values"), jax_compile.find("ppo_recurrent.gae")
    assert train.calls == 3 and train.traces == 1 and train.retraces == 0
    assert act.calls == 48 and act.traces == 1 and act.retraces == 0
    assert values.calls == 3 and values.traces == 1 and values.retraces == 0
    assert gae.retraces == 0


# the second family's experiment: the cut's five layers (sliding, sliding, sliding, sliding, full) at width 32, a window of 4
# (episodes of 16 steps as the first family's, so that the module's one `ppo_recurrent.gae` sees the shapes it has seen)
TINY_TRINITY = [
    "exp=ppo_recurrent_trinity_tokens",
    "algo.lm.hidden_size=32", "algo.lm.intermediate_size=48", "algo.lm.moe_intermediate_size=24",
    "algo.lm.num_attention_heads=4", "algo.lm.num_key_value_heads=1", "algo.lm.head_dim=8",
    "algo.lm.num_experts=16", "algo.lm.num_experts_per_tok=4", "algo.lm.experts_held=4", "algo.lm.vocab_held=64",
    "algo.lm.sliding_window=4", "algo.lm.query_block=8", "algo.lm.head_chunk=8",
    "env.wrapper.prompt_tokens=4", "env.wrapper.sampled_tokens=12", "algo.rollout_steps=16",
    "fabric.precision=32-true", "fabric.devices=1",
    "metric.log_level=0", "checkpoint.save_last=False", "algo.run_test=False",
]


@pytest.mark.parametrize("player_on_host", [True, False])
def test_the_second_family_s_experiment_runs_through_the_cli_with_no_retrace(tmp_path, monkeypatch, player_on_host):
    """Three updates of ``exp=ppo_recurrent_trinity_tokens`` (two envs, 16-step episodes: four times round the
    sliding layers' ring of 4, with the resets between episodes): acting through the two sizes of cache in one
    carried state, whole episodes as training sequences, the train call; each program traced once."""
    monkeypatch.chdir(tmp_path)
    gae_before = getattr(jax_compile.find("ppo_recurrent.gae"), "retraces", 0)  # one function a process, whatever ran before
    run(overrides=TINY_TRINITY + ["algo.total_steps=96", f"fabric.player_on_host={player_on_host}"])
    train, act = jax_compile.find("ppo_recurrent.train"), jax_compile.find("ppo_recurrent.act_packed")
    values, gae = jax_compile.find("ppo_recurrent.values"), jax_compile.find("ppo_recurrent.gae")
    assert train.calls == 3 and train.traces == 1 and train.retraces == 0
    assert act.calls == 48 and act.traces == 1 and act.retraces == 0
    assert values.calls == 3 and values.traces == 1 and values.retraces == 0
    assert gae.retraces == gae_before


# the third family's experiment: the cut's four layers (full, sliding, sliding, sliding) at width 32, seven query heads on one
# key-value head, a window of 4, and the experiment's own one sequence a step (a microbatch of one whole episode)
TINY_SMALLTHINKER = [
    "exp=ppo_recurrent_smallthinker_tokens",
    "algo.lm.hidden_size=32", "algo.lm.moe_intermediate_size=24",
    "algo.lm.num_attention_heads=7", "algo.lm.num_key_value_heads=1", "algo.lm.head_dim=8",
    "algo.lm.num_experts=16", "algo.lm.num_experts_per_tok=3", "algo.lm.experts_held=4", "algo.lm.vocab_held=64",
    "algo.lm.sliding_window=4", "algo.lm.query_block=8", "algo.lm.head_chunk=8",
    "env.wrapper.prompt_tokens=4", "env.wrapper.sampled_tokens=12", "algo.rollout_steps=16",
    "fabric.precision=32-true", "fabric.devices=1",
    "metric.log_level=0", "checkpoint.save_last=False", "algo.run_test=False",
]


@pytest.mark.parametrize("player_on_host", [True, False])
def test_the_third_family_s_experiment_runs_through_the_cli_with_no_retrace(tmp_path, monkeypatch, player_on_host):
    """Three updates of ``exp=ppo_recurrent_smallthinker_tokens`` (one env, 16-step episodes: four times round the
    sliding layers' ring of 4; the router reads each block's input in the decode step as in the whole pass): acting,
    the value and the train call on one sequence a step, each program traced once."""
    monkeypatch.chdir(tmp_path)
    run(overrides=TINY_SMALLTHINKER + ["algo.total_steps=48", f"fabric.player_on_host={player_on_host}"])
    train, act = jax_compile.find("ppo_recurrent.train"), jax_compile.find("ppo_recurrent.act_packed")
    values = jax_compile.find("ppo_recurrent.values")
    assert train.calls == 3 and train.traces == 1 and train.retraces == 0
    assert act.calls == 48 and act.traces == 1 and act.retraces == 0
    assert values.calls == 3 and values.traces == 1 and values.retraces == 0


def test_the_third_family_in_bf16_mixed_and_its_greedy_test_episode_run(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    run(overrides=TINY_SMALLTHINKER + ["dry_run=True", "fabric.precision=bf16-mixed", "fabric.player_on_host=True", "algo.run_test=True"])


def test_the_second_family_in_bf16_mixed_and_its_greedy_test_episode_run(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    run(overrides=TINY_TRINITY + ["dry_run=True", "fabric.precision=bf16-mixed", "fabric.player_on_host=True", "algo.run_test=True"])


def test_bf16_mixed_and_the_greedy_test_episode_run(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    run(overrides=TINY + ["dry_run=True", "fabric.precision=bf16-mixed", "algo.run_test=True"])


@pytest.mark.parametrize(
    "overrides,message",
    [
        (["algo.reset_recurrent_state_on_done=False"], "reset_recurrent_state_on_done=True"),
        (["algo.lm.max_positions=8"], "has to fit the model's positions"),
        # episodes of 16 steps in rollouts of 24: the second rollout would start in the middle of an episode
        (["algo.rollout_steps=24", "algo.total_steps=96"], "must start at a reset"),
        # episodes of 16 steps cut into sequences of 8: a sequence would start without the state before it
        (["algo.per_rank_sequence_length=8", "algo.lm.max_positions=16"], "must start at a reset"),
    ],
)
def test_sequences_that_cannot_start_at_resets_are_refused_by_name(tmp_path, monkeypatch, overrides, message):
    monkeypatch.chdir(tmp_path)
    with pytest.raises(ValueError, match=message):
        run(overrides=TINY + ["dry_run=True"] + overrides)


# ---------------------------------------------------------------- the LSTM through the seam


def _parent_make_train_fn(agent, tx, cfg, runtime, obs_keys, cnn_keys):
    """``make_train_fn`` as it stood before the seam (commit 09f8d47), kept word for word but
    for the player sync: the LSTM's state handed to ``agent.apply`` from ``prev_hx`` / ``prev_cx``."""
    update_epochs = int(cfg.algo.update_epochs)
    n_batches = max(int(cfg.algo.per_rank_num_batches), 1)
    data_sharding = NamedSharding(runtime.mesh, P(None, "data"))

    def _masked_mean(x, mask):
        return (x * mask).sum() / jnp.clip(mask.sum(), 1, None)

    def loss_fn(params, batch, clip_coef, ent_coef):
        norm_obs = normalize_obs(batch, cnn_keys, obs_keys)
        actions = (
            jnp.split(batch["actions"], np.cumsum(agent.actions_dim)[:-1].tolist(), axis=-1)
            if len(agent.actions_dim) > 1
            else [batch["actions"]]
        )
        mask = batch["mask"]
        actor_outs, values, _ = agent.apply(
            params, norm_obs, batch["prev_actions"], (batch["prev_hx"], batch["prev_cx"]), mask
        )
        new_logprobs, entropy = evaluate_actions(actor_outs, actions, agent.is_continuous, agent.distribution)
        advantages = batch["advantages"]
        pg = policy_loss(new_logprobs, batch["logprobs"], advantages, clip_coef, "none")
        pg_loss = _masked_mean(pg, mask)
        v_loss = _masked_mean((values - batch["returns"]) ** 2, mask)
        ent_loss = -_masked_mean(entropy, mask)
        total = pg_loss + cfg.algo.vf_coef * v_loss + cfg.algo.ent_coef * ent_loss
        return total, (pg_loss, v_loss, ent_loss)

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def train(params, opt_state, data, key, clip_coef, ent_coef, lr_scale):
        n_seq = next(iter(data.values())).shape[1]
        batch_size = max(n_seq // n_batches, 1)
        n_mb = n_seq // batch_size
        epoch_keys = jax.random.split(key, update_epochs)
        perms = jnp.stack([jax.random.permutation(k, n_seq)[: n_mb * batch_size] for k in epoch_keys])
        perms = perms.reshape(update_epochs * n_mb, batch_size)

        def minibatch_step(carry, idx):
            params, opt_state = carry
            batch = jax.tree_util.tree_map(
                lambda v: jax.lax.with_sharding_constraint(jnp.take(v, idx, axis=1), data_sharding), data
            )
            batch = dict(batch)
            batch["prev_hx"] = batch["prev_hx"][0]
            batch["prev_cx"] = batch["prev_cx"][0]
            (loss, (pg, vl, ent)), grads = grad_fn(params, batch, clip_coef, ent_coef)
            gnorm = optax.global_norm(grads)
            updates, new_opt_state = tx.update(grads, opt_state, params)
            updates = jax.tree_util.tree_map(lambda u: u * lr_scale, updates)
            new_params = optax.apply_updates(params, updates)
            return (new_params, new_opt_state), jnp.stack([pg, vl, ent, gnorm])

        (params, opt_state), losses = jax.lax.scan(minibatch_step, (params, opt_state), perms)
        return params, opt_state, losses.mean(axis=0)

    return jax.jit(train)


@pytest.mark.parametrize("env_id", ["discrete_dummy", "continuous_dummy", "multidiscrete_dummy"])
def test_lstm_train_call_is_bitwise_what_it_was_before_the_seam(env_id):
    import gymnasium as gym

    cfg = compose(config_name="config", overrides=[
        "exp=ppo_recurrent", "env=dummy", f"env.id={env_id}", "algo.mlp_keys.encoder=[state]", "algo.dense_units=8",
        "algo.mlp_layers=1", "algo.rnn.lstm.hidden_size=8", "algo.per_rank_num_batches=2", "algo.update_epochs=2",
        "fault_tolerance.nonfinite.policy=off",
    ])
    runtime = build_runtime(cfg.fabric)
    obs_space = gym.spaces.Dict({"state": gym.spaces.Box(-20, 20, (10,), np.float32)})
    actions_dim, is_continuous = {
        "discrete_dummy": ((4,), False), "continuous_dummy": ((2,), True), "multidiscrete_dummy": ((3, 2), False),
    }[env_id]
    agent, params, player = build_agent(runtime, actions_dim, is_continuous, cfg, obs_space)
    tx = with_clipping(instantiate(dict(cfg.algo.optimizer))(), cfg.algo.max_grad_norm)
    T, n_seq, width = 6, 4, sum(actions_dim)
    rng = np.random.default_rng(0)
    if is_continuous:
        actions = rng.normal(size=(T, n_seq, width)).astype(np.float32)
    else:
        actions = np.concatenate([np.eye(d, dtype=np.float32)[rng.integers(0, d, (T, n_seq))] for d in actions_dim], -1)
    mask = np.ones((T, n_seq, 1), np.float32)
    mask[4:, 1] = 0.0
    f = lambda *shape: rng.normal(size=shape).astype(np.float32)  # noqa: E731
    data = {
        "state": f(T, n_seq, 10), "actions": actions, "prev_actions": np.roll(actions, 1, 0), "mask": mask,
        "prev_hx": f(T, n_seq, 8), "prev_cx": f(T, n_seq, 8), "logprobs": f(T, n_seq, 1), "values": f(T, n_seq, 1),
        "advantages": f(T, n_seq, 1), "returns": f(T, n_seq, 1), "rewards": f(T, n_seq, 1), "dones": np.zeros((T, n_seq, 1), np.float32),
    }
    data = {k: jnp.asarray(v) for k, v in data.items()}
    args = (data, jax.random.PRNGKey(3), jnp.float32(0.2), jnp.float32(0.001), jnp.float32(1.0))
    copy = lambda tree: jax.tree_util.tree_map(jnp.copy, tree)  # noqa: E731  (the program's call donates)
    new = ppo_recurrent.make_train_fn(agent, tx, cfg, runtime, ["state"], [], None)
    assert resilience_is_off(cfg)
    got_params, got_opt, _, got = new(copy(params), tx.init(params), *args)
    want_params, want_opt, want = _parent_make_train_fn(agent, tx, cfg, runtime, ["state"], [])(copy(params), tx.init(params), *args)
    for a, b in zip(jax.tree_util.tree_leaves((got_params, got_opt)), jax.tree_util.tree_leaves((want_params, want_opt))):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    names = ("Loss/policy_loss", "Loss/value_loss", "Loss/entropy_loss", "Grads/global_norm")
    assert [float(got[n]) for n in names] == [float(x) for x in want]
    assert set(got) == {*names, "Resilience/nonfinite_skips"}  # an LSTM adds no counters of its own
    # the player's side of the seam: the stored rows and the reset are the tuple's own
    states = player.initial_states(8)
    assert set(player.state_rows(states)) == {"prev_hx", "prev_cx"}
    assert agent.action_width == width and not agent.starts_at_reset


def resilience_is_off(cfg) -> bool:
    from sheeprl_tpu.core import resilience

    return not resilience.guard_enabled(resilience.resolve(cfg))
