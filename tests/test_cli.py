"""CLI entrypoint surface: presets, overrides, and a short real run."""

import dataclasses

import pytest

from actor_critic_algs_on_tensorflow_tpu.cli import train as cli


def test_presets_cover_all_algos():
    algos = {algo for algo, _ in cli.PRESETS.values()}
    # The five baseline algos (BASELINE.json:7-11) must all have a
    # preset; beyond-parity additions (td3) ride along.
    assert algos == {"a2c", "ppo", "ddpg", "td3", "sac", "impala"}


def test_make_config_preset_and_overrides():
    args = cli.build_parser().parse_args(
        ["--preset", "ppo-pong", "--set", "lr=1e-3", "--set",
         "hidden_sizes=32,32", "--set", "vf_clip=false", "--total-steps", "999"]
    )
    algo, cfg = cli.make_config(args)
    assert algo == "ppo"
    assert cfg.torso == "nature_cnn" and cfg.frame_stack == 4
    assert cfg.lr == 1e-3
    assert cfg.hidden_sizes == (32, 32)
    assert cfg.vf_clip is False
    assert cfg.total_env_steps == 999


def test_make_config_flicker_preset():
    """ppo-flicker-pong: the recurrent Atari-class POMDP preset pairs
    the flicker env with frame_stack=1 (memory, not stacking, must
    carry state) and the decayed env-sliced recurrent schedule."""
    args = cli.build_parser().parse_args(["--preset", "ppo-flicker-pong"])
    algo, cfg = cli.make_config(args)
    assert algo == "ppo"
    assert cfg.env == "PongFlickerTPU-v0"
    assert cfg.recurrent is True and cfg.lstm_size == 256
    assert cfg.frame_stack == 1
    assert cfg.shuffle == "env" and cfg.num_minibatches == 4
    assert cfg.lr_decay is True


def test_preempt_save_flag_and_sentinel_overrides():
    """--preempt-save defaults on (pod preemptions are the steady
    state), --no-preempt-save opts out; sentinel knobs ride --set."""
    args = cli.build_parser().parse_args(["--preset", "impala-cartpole"])
    assert args.preempt_save is True
    args = cli.build_parser().parse_args(
        ["--preset", "impala-cartpole", "--no-preempt-save",
         "--set", "max_rollbacks=5", "--set", "numerics_guards=false",
         "--set", "quarantine_threshold=2"]
    )
    assert args.preempt_save is False
    _, cfg = cli.make_config(args)
    assert cfg.max_rollbacks == 5
    assert cfg.numerics_guards is False
    assert cfg.quarantine_threshold == 2


def test_controlplane_flags_parse_and_validate():
    """--standby/--coordinate-preemption/--redirector (ISSUE 4): spec
    parsing and the impala-only / dependency guards."""
    # Specs must carry explicit ports (they name peers, not binds).
    with pytest.raises(SystemExit, match="explicit port"):
        cli.parse_hostport("10.0.0.1", "--standby")
    assert cli.parse_hostport("10.0.0.1:7000", "--standby") == (
        "10.0.0.1", 7000,
    )
    with pytest.raises(SystemExit, match="lead:N@HOST:PORT"):
        cli.make_coordinator("sideways:1")
    with pytest.raises(SystemExit, match="follower count"):
        cli.make_coordinator("lead@127.0.0.1:9000")
    with pytest.raises(SystemExit, match="unknown role"):
        cli.make_coordinator("boss:2@127.0.0.1:9000")
    # Non-impala algos reject the control-plane flags outright.
    # PR 14: --standby also serves the off-policy trainers; a2c still
    # rejects it outright.
    args = cli.build_parser().parse_args(
        ["--algo", "a2c", "--standby", "127.0.0.1:7000"]
    )
    with pytest.raises(SystemExit, match="impala and the off-policy"):
        cli._run(args, "a2c", None, None)
    args = cli.build_parser().parse_args(
        ["--algo", "a2c", "--coordinate-preemption", "follow@h:1"]
    )
    with pytest.raises(SystemExit, match="impala-only"):
        cli._run(args, "a2c", None, None)
    # --redirector rides --standby; --standby needs the tail source.
    args = cli.build_parser().parse_args(
        ["--preset", "impala-cartpole", "--redirector", "7100"]
    )
    with pytest.raises(SystemExit, match="requires --standby"):
        cli._run(args, "impala", None, None)
    args = cli.build_parser().parse_args(
        ["--preset", "impala-cartpole", "--standby", "127.0.0.1:7000"]
    )
    _, cfg = cli.make_config(args)
    with pytest.raises(SystemExit, match="checkpoint-dir"):
        cli._run(args, "impala", cfg, None)


def test_standby_quorum_flags_parse_and_validate():
    """--standby-rank/--standby-peers (ISSUE 10): the quorum flags'
    parsing, dependency guards, and rank-range validation."""
    # Quorum flags ride --standby.
    args = cli.build_parser().parse_args(
        ["--preset", "impala-cartpole", "--standby-rank", "1"]
    )
    with pytest.raises(SystemExit, match="require --standby"):
        cli._run(args, "impala", None, None)
    args = cli.build_parser().parse_args(
        ["--preset", "impala-cartpole",
         "--standby-peers", "h1:7001,h2:7001"]
    )
    with pytest.raises(SystemExit, match="require --standby"):
        cli._run(args, "impala", None, None)
    # A rank without the peers list it indexes is meaningless.
    args = cli.build_parser().parse_args(
        ["--preset", "impala-cartpole",
         "--standby", "127.0.0.1:7000", "--standby-rank", "1",
         "--checkpoint-dir", "/tmp/nope"]
    )
    _, cfg = cli.make_config(args)
    with pytest.raises(SystemExit, match="needs --standby-peers"):
        cli._run(args, "impala", cfg, None)
    # Rank outside the peers list.
    args = cli.build_parser().parse_args(
        ["--preset", "impala-cartpole",
         "--standby", "127.0.0.1:7000", "--standby-rank", "3",
         "--standby-peers", "h1:7001,h2:7001",
         "--checkpoint-dir", "/tmp/nope"]
    )
    _, cfg = cli.make_config(args)
    with pytest.raises(SystemExit, match="outside the 2-entry"):
        cli._run(args, "impala", cfg, None)
    # Peers entries need explicit ports (they name peers, not binds).
    args = cli.build_parser().parse_args(
        ["--preset", "impala-cartpole",
         "--standby", "127.0.0.1:7000",
         "--standby-peers", "h1,h2:7001",
         "--checkpoint-dir", "/tmp/nope"]
    )
    _, cfg = cli.make_config(args)
    with pytest.raises(SystemExit, match="explicit port"):
        cli._run(args, "impala", cfg, None)


def test_quorum_bind_must_pin_own_peers_entry():
    """A quorum standby's listener must live exactly where the peers
    list says it does (elections and fallback walks probe that
    address); an ephemeral or mismatched --learner-bind is refused."""
    base = [
        "--preset", "impala-cartpole",
        "--standby", "127.0.0.1:7000", "--standby-rank", "1",
        "--standby-peers", "h0:7001,h1:7002",
        "--checkpoint-dir", "/tmp/nope",
    ]
    # No --learner-bind at all: the default ephemeral port mismatches.
    args = cli.build_parser().parse_args(base)
    _, cfg = cli.make_config(args)
    with pytest.raises(SystemExit, match="pin this standby's own"):
        cli._run(args, "impala", cfg, None)
    # Wrong port: same refusal.
    args = cli.build_parser().parse_args(
        base + ["--learner-bind", "0.0.0.0:7009"]
    )
    _, cfg = cli.make_config(args)
    with pytest.raises(SystemExit, match="pin this standby's own"):
        cli._run(args, "impala", cfg, None)
    # Sharded standby without a pinned bind: the port..port+N-1
    # listener contract cannot ride ephemeral ports.
    args = cli.build_parser().parse_args(
        ["--preset", "impala-cartpole",
         "--standby", "127.0.0.1:7000", "--set", "shard_count=2",
         "--checkpoint-dir", "/tmp/nope"]
    )
    _, cfg = cli.make_config(args)
    with pytest.raises(SystemExit, match="explicit --learner-bind"):
        cli._run(args, "impala", cfg, None)


def test_redirector_rejected_for_sharded_standby():
    """One redirector has one target: with shard_count > 1 its
    last-wins re-point would route every actor to shard N-1 and
    starve the rest — refused at configuration time."""
    args = cli.build_parser().parse_args(
        ["--preset", "impala-cartpole",
         "--standby", "127.0.0.1:7000", "--redirector", "7100",
         "--set", "shard_count=2", "--checkpoint-dir", "/tmp/nope"]
    )
    _, cfg = cli.make_config(args)
    with pytest.raises(SystemExit, match="single-stack"):
        cli._run(args, "impala", cfg, None)


def test_election_knobs_coerce_via_set():
    """The quorum knobs ride --set with the config's type coercion
    (the satellite alongside the sentinel-knob test above)."""
    args = cli.build_parser().parse_args(
        ["--preset", "impala-cartpole",
         "--set", "standby_never_seen_grace_s=2.5",
         "--set", "election_probe_timeout_s=0.25",
         "--set", "election_probe_attempts=5"]
    )
    _, cfg = cli.make_config(args)
    assert cfg.standby_never_seen_grace_s == 2.5
    assert cfg.election_probe_timeout_s == 0.25
    assert cfg.election_probe_attempts == 5
    # Defaults: grace 0 = "use 10x the takeover deadline".
    _, cfg = cli.make_config(
        cli.build_parser().parse_args(["--preset", "impala-cartpole"])
    )
    assert cfg.standby_never_seen_grace_s == 0.0
    assert cfg.election_probe_attempts == 3


def test_rollout_mode_coerces_via_set():
    """The device-resident fast path rides --set with the config's
    string coercion (ISSUE 11 satellite)."""
    args = cli.build_parser().parse_args(
        ["--preset", "impala-cartpole", "--set", "rollout_mode=device",
         "--set", "mixed_device_per_wire=3"]
    )
    _, cfg = cli.make_config(args)
    assert cfg.rollout_mode == "device"
    assert cfg.mixed_device_per_wire == 3
    # Default stays the classic host-ingest topology.
    _, cfg = cli.make_config(
        cli.build_parser().parse_args(["--preset", "impala-cartpole"])
    )
    assert cfg.rollout_mode == "host"


def test_rollout_mode_flag_refusals():
    """rollout_mode='device'/'mixed' reject the wire-topology flags
    with the fix in the message (ISSUE 11 satellite): --standby,
    --shard, and the actor-process mismatches."""
    def _cfg_for(extra):
        args = cli.build_parser().parse_args(
            ["--preset", "impala-cartpole",
             "--set", "rollout_mode=device"] + extra
        )
        return args, cli.make_config(args)[1]

    args, cfg = _cfg_for(
        ["--standby", "127.0.0.1:7000", "--checkpoint-dir", "/tmp/nope"]
    )
    with pytest.raises(SystemExit, match="rollout_mode='host'"):
        cli._run(args, "impala", cfg, None)
    args, cfg = _cfg_for(["--actor-processes", "--shard", "2"])
    with pytest.raises(SystemExit, match="already shards envs"):
        cli._run(args, "impala", cfg, None)
    args, cfg = _cfg_for(["--actor-processes"])
    with pytest.raises(SystemExit, match="drop --actor-processes"):
        cli._run(args, "impala", cfg, None)
    # mixed without a wire fleet to interleave with.
    args = cli.build_parser().parse_args(
        ["--preset", "impala-cartpole", "--set", "rollout_mode=mixed"]
    )
    _, cfg = cli.make_config(args)
    with pytest.raises(SystemExit, match="pass --actor-processes"):
        cli._run(args, "impala", cfg, None)


def test_coordinator_leader_follower_roundtrip_via_cli_specs():
    """make_coordinator builds a working leader/follower pair."""
    import threading

    leader = cli.make_coordinator("lead:1@127.0.0.1:0")
    try:
        follower = cli.make_coordinator(f"follow@127.0.0.1:{leader.port}")
        out = {}
        t = threading.Thread(
            target=lambda: out.setdefault(
                "agreed", follower.decide(7, timeout_s=10.0)
            ),
            daemon=True,
        )
        t.start()
        assert leader.decide(3, timeout_s=10.0) == 7
        t.join(timeout=10.0)
        assert out["agreed"] == 7
        follower.close()
    finally:
        leader.close()


def test_unknown_override_rejected():
    args = cli.build_parser().parse_args(
        ["--algo", "a2c", "--set", "nope=1"]
    )
    with pytest.raises(SystemExit, match="unknown config field"):
        cli.make_config(args)


def test_cli_end_to_end_a2c(capsys):
    rc = cli.main(
        ["--algo", "a2c", "--env", "CartPole-v1", "--total-steps", "2048",
         "--set", "num_envs=16", "--set", "rollout_length=8",
         "--log-interval", "8"]
    )
    assert rc == 0
    out = capsys.readouterr().out
    assert "steps_per_sec" in out and "done" in out


@pytest.mark.slow
def test_cli_checkpoint_resume_roundtrip(tmp_path, capsys):
    common = [
        "--algo", "a2c", "--env", "CartPole-v1",
        "--set", "num_envs=16", "--set", "rollout_length=8",
        "--checkpoint-dir", str(tmp_path / "ck"),
        "--checkpoint-interval", "4", "--log-interval", "100",
    ]
    assert cli.main(common + ["--total-steps", "1024"]) == 0
    assert cli.main(common + ["--total-steps", "2048", "--resume"]) == 0
    out = capsys.readouterr().out
    assert "resumed from step" in out


def test_cli_tensorboard_output(tmp_path):
    from actor_critic_algs_on_tensorflow_tpu.utils import tensorboard as tb
    import os

    rc = cli.main(
        ["--algo", "a2c", "--env", "CartPole-v1", "--total-steps", "1024",
         "--set", "num_envs=16", "--set", "rollout_length=8",
         "--log-interval", "4", "--tensorboard-dir", str(tmp_path / "tb")]
    )
    assert rc == 0
    files = os.listdir(tmp_path / "tb")
    assert len(files) == 1
    scalars = tb.read_scalars(str(tmp_path / "tb" / files[0]))
    assert "loss" in scalars and "steps_per_sec" in scalars


def test_cli_train_then_eval_roundtrip(tmp_path, capsys):
    common = [
        "--algo", "a2c", "--env", "CartPole-v1",
        "--set", "num_envs=16", "--set", "rollout_length=8",
        "--checkpoint-dir", str(tmp_path / "ck"),
    ]
    assert cli.main(common + ["--total-steps", "1024"]) == 0
    assert cli.main(
        common + ["--eval", "--eval-envs", "8", "--eval-steps", "64"]
    ) == 0
    out = capsys.readouterr().out
    assert "[eval] avg_return=" in out
    assert cli.main(
        common + ["--eval", "--stochastic",
                  "--eval-envs", "8", "--eval-steps", "64"]
    ) == 0


def test_cli_eval_render_writes_episode_artifact(tmp_path, capsys):
    # The "enjoy script" artifact: vector envs record episode.npy
    # (image envs write episode.gif via the same path).
    common = [
        "--algo", "a2c", "--env", "CartPole-v1",
        "--set", "num_envs=8", "--set", "rollout_length=8",
        "--checkpoint-dir", str(tmp_path / "ck"),
    ]
    assert cli.main(common + ["--total-steps", "512"]) == 0
    render = tmp_path / "render"
    assert cli.main(
        common + ["--eval", "--eval-envs", "4", "--eval-steps", "48",
                  "--render-dir", str(render)]
    ) == 0
    import numpy as np

    ep = np.load(render / "episode.npy")
    assert ep.ndim == 2 and ep.shape[1] == 4 and 1 <= ep.shape[0] <= 48
    out = capsys.readouterr().out
    assert "episode.npy" in out


def test_cli_eval_requires_checkpoint_dir():
    with pytest.raises(SystemExit, match="requires --checkpoint-dir"):
        cli.main(["--algo", "a2c", "--eval"])


@pytest.mark.slow
def test_cli_impala_checkpoint_resume_eval(tmp_path, capsys):
    common = [
        "--preset", "impala-cartpole",
        "--set", "num_actors=2", "--set", "envs_per_actor=4",
        "--set", "rollout_length=8", "--set", "batch_trajectories=2",
        "--set", "num_devices=1",
        "--checkpoint-dir", str(tmp_path / "ck"),
    ]
    # checkpoint-interval divides the 4 learner steps: the loop saves
    # the final step itself, exercising the duplicate-save guard.
    assert cli.main(
        common + ["--total-steps", "256", "--log-interval", "2",
                  "--checkpoint-interval", "2"]
    ) == 0
    # Resume trains only the remainder of the doubled budget.
    assert cli.main(
        common + ["--total-steps", "512", "--log-interval", "2", "--resume"]
    ) == 0
    out = capsys.readouterr().out
    assert "resumed from step 256" in out
    assert "done: learner steps=8" in out
    assert cli.main(
        common + ["--eval", "--eval-envs", "4", "--eval-steps", "32"]
    ) == 0
    out = capsys.readouterr().out
    assert "[eval] avg_return=" in out


@pytest.mark.slow
def test_evaluate_checkpoint_sac(tmp_path):
    """Off-policy eval path: params.actor routing + tanh squash."""
    from actor_critic_algs_on_tensorflow_tpu.algos.evaluation import (
        evaluate_checkpoint,
    )

    rc = cli.main(
        ["--algo", "sac", "--env", "Pendulum-v1", "--total-steps", "512",
         "--set", "num_envs=8", "--set", "num_devices=1",
         "--set", "replay_capacity=2048", "--set", "warmup_env_steps=128",
         "--checkpoint-dir", str(tmp_path / "ck"), "--log-interval", "100"]
    )
    assert rc == 0
    import dataclasses as dc

    from actor_critic_algs_on_tensorflow_tpu.algos.sac import SACConfig

    cfg = SACConfig(
        env="Pendulum-v1", num_envs=8, num_devices=1,
        replay_capacity=2048, warmup_env_steps=128, total_env_steps=512,
    )
    mean_ret, per_env, frac = evaluate_checkpoint(
        "sac", cfg, str(tmp_path / "ck"), num_envs=4, max_steps=32
    )
    import numpy as np

    assert np.isfinite(mean_ret)
    assert per_env.shape == (4,)


@pytest.mark.slow
def test_cli_td3_train_then_eval(tmp_path, capsys):
    """TD3 through the full CLI surface: train, checkpoint, eval —
    with observation normalization on, so the eval leg restores and
    applies the off-policy ``params.obs_rms`` stats."""
    common = [
        "--algo", "td3", "--env", "Pendulum-v1",
        "--set", "num_envs=8", "--set", "num_devices=1",
        "--set", "replay_capacity=2048", "--set", "warmup_env_steps=128",
        "--set", "normalize_obs=True",
        "--checkpoint-dir", str(tmp_path / "ck"),
    ]
    assert cli.main(
        common + ["--total-steps", "512", "--log-interval", "100"]
    ) == 0
    assert cli.main(
        common + ["--eval", "--eval-envs", "4", "--eval-steps", "32"]
    ) == 0
    out = capsys.readouterr().out
    assert "[eval] avg_return=" in out


@pytest.mark.slow
def test_cli_finetune_chain_semantics(tmp_path, capsys):
    """The reward-21 chain's stage transitions (scripts/reward21_chain.sh)
    at tiny scale: resume across a num_minibatches/lr/ent_coef schedule
    change, then resume the copied checkpoint with the env switched to
    PongServeTPU-v0 (identical dynamics/spaces, adversarial resets),
    then eval on the STANDARD env."""
    import shutil

    ck, serve = tmp_path / "ck", tmp_path / "serve"
    common = [
        "--preset", "ppo-pong", "--seed", "0",
        "--set", "num_envs=4", "--set", "rollout_length=8",
        "--set", "num_devices=1", "--log-interval", "100",
    ]
    assert cli.main(
        common + ["--checkpoint-dir", str(ck), "--total-steps", "64"]
    ) == 0
    # Stage-4-style schedule change on resume: optimizer state restores
    # across it (mb/lr/ent live in the jitted update, not the state).
    assert cli.main(
        common + ["--checkpoint-dir", str(ck), "--resume",
                  "--total-steps", "128",
                  "--set", "num_minibatches=4", "--set", "lr=1e-4",
                  "--set", "ent_coef=0.0"]
    ) == 0
    out = capsys.readouterr().out
    assert "resumed from step" in out
    # Stage-8-style targeted fine-tune: copy the chain, switch envs.
    shutil.copytree(ck, serve)
    assert cli.main(
        common + ["--checkpoint-dir", str(serve), "--resume",
                  "--env", "PongServeTPU-v0", "--total-steps", "192",
                  "--set", "num_minibatches=4", "--set", "lr=1e-4"]
    ) == 0
    # Eval the fine-tuned artifact on the standard env (the preset's).
    assert cli.main(
        ["--preset", "ppo-pong", "--set", "num_envs=4",
         "--set", "rollout_length=8", "--set", "num_devices=1",
         "--checkpoint-dir", str(serve),
         "--eval", "--eval-envs", "4", "--eval-steps", "64"]
    ) == 0
    out = capsys.readouterr().out
    assert "[eval] avg_return=" in out


def test_eval_return_hist_formatting():
    import numpy as np

    from actor_critic_algs_on_tensorflow_tpu.cli.train import (
        format_return_hist,
    )

    # Integer-valued, compact: one count per distinct value, sorted.
    line = format_return_hist(np.asarray([21.0, 19.0, 21.0, 20.0]))
    assert line == "[eval] return_hist 19:1 20:1 21:2"
    # Float-valued returns (MuJoCo): 8 equal-width bins, empty bins
    # dropped, LAST bin closed (it holds the max).
    line = format_return_hist(np.asarray([-1422.4, -1266.3]))
    assert line == "[eval] return_hist [-1422,-1403):1 [-1286,-1266]:1"
    # High-cardinality integers take the binned path too.
    line = format_return_hist(np.arange(40.0))
    assert line.startswith("[eval] return_hist [0,5):5")
    assert line.endswith("[34,39]:5")
    # Every episode at the same return: a single degenerate cell.
    assert format_return_hist(np.asarray([-7.0, -7.0])) == (
        "[eval] return_hist -7:2"
    )


@pytest.mark.parametrize("limit,fits,says", [
    (None, True, False),            # a backend that reports no capacity
    (2**40, True, False),           # room for the state three times
    (2**16, False, True),           # the state alone is more than that
])
def test_sentinel_snapshot_only_where_the_state_fits_twice(
    monkeypatch, capsys, limit, fits, says
):
    """The rollback target is a second copy of the train state: where
    the device cannot hold it (the sequence-core presets on one chip)
    the run goes on with the guard bit logged and no snapshot, instead
    of dying in ``sentinel.seed``."""
    import types

    import jax

    device = types.SimpleNamespace(memory_stats=lambda: (
        None if limit is None else {"bytes_limit": limit}
    ))
    monkeypatch.setattr(jax, "local_devices", lambda *a, **k: [device])
    rc = cli.main(["--preset", "a2c-cartpole", "--total-steps", "640",
                   "--set", "num_envs=8"])
    out = capsys.readouterr().out
    assert rc == 0 and "[train] done" in out
    assert ("nothing is rolled back" in out) == says
    assert ("health_finite=1" in out)
    from actor_critic_algs_on_tensorflow_tpu.algos.a2c import (
        A2CConfig,
        make_a2c,
    )

    cfg = A2CConfig(env="CartPole-v1", num_envs=8)
    assert cli._snapshot_fits(make_a2c(cfg), cfg) == fits


def test_the_sdar_presets_resolve_and_need_no_rollback_copy(monkeypatch):
    """Both block-diffusion presets are PPO configs whose env and model
    agree on the block; one episode is one rollout; and the cell's
    train state (646 M parameters with Adam's moments, 7.5 GiB) does not
    fit a 16 GB chip beside a rollback copy of itself, so the sentinel's
    snapshot is left out as for the other sequence-core presets."""
    import types

    import jax

    from actor_critic_algs_on_tensorflow_tpu.algos.ppo import (
        PPOConfig,
        make_ppo,
    )

    for name in ("ppo-sdar-turns", "ppo-sdar-tiny"):
        algo, base = cli.PRESETS[name]
        cfg = PPOConfig(**dict(base, num_devices=1))
        env, model = cfg.env_params, cfg.seq_model
        assert algo == "ppo" and cfg.torso == "sdar" and cfg.recurrent
        assert env.episode_length == cfg.rollout_length
        assert (env.vocab_size, env.block_length, env.mask_id) == (
            model.vocab_size, model.block_length, model.mask_token_id
        )
        assert env.denoise_steps == model.denoising_steps
    fns = make_ppo(cfg)  # the tiny one
    assert cli._snapshot_fits(fns, cfg)  # the CPU reports no capacity
    algo, base = cli.PRESETS["ppo-sdar-turns"]
    cfg = PPOConfig(**dict(base, num_devices=1))
    fns = make_ppo(cfg)
    state = jax.eval_shape(fns.init, jax.random.PRNGKey(0))
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(state.params))
    assert n_params == 645_625_345
    assert state.carry["core"]["layers"].shape == (128, 6, 192, 1024)
    assert state.obs.shape == (128, 4)
    chip = types.SimpleNamespace(
        memory_stats=lambda: {"bytes_limit": int(15.75 * 2**30)}
    )
    monkeypatch.setattr(jax, "local_devices", lambda *a, **k: [chip])
    assert not cli._snapshot_fits(fns, cfg)


def test_the_granite_presets_resolve_and_need_no_rollback_copy(monkeypatch):
    """Both Granite hybrid presets are PPO configs whose env and model
    agree on the vocabulary; one episode is one rollout, a whole number
    of the scan's chunks and more than one; the cell's carry is four
    arrays with the env axis first; and its train state (772 M
    parameters with Adam's moments, 8.6 GiB) does not fit a 16 GB chip
    beside a rollback copy of itself, so the sentinel's snapshot is
    left out as for the other sequence-core presets."""
    import types

    import jax

    from actor_critic_algs_on_tensorflow_tpu.algos.ppo import (
        PPOConfig,
        make_ppo,
    )

    for name in ("ppo-granite-recall", "ppo-granite-tiny"):
        algo, base = cli.PRESETS[name]
        cfg = PPOConfig(**dict(base, num_devices=1))
        env, model = cfg.env_params, cfg.seq_model
        assert algo == "ppo" and cfg.torso == "granite_hybrid"
        assert cfg.recurrent and cfg.num_minibatches == 4
        assert env.episode_length == cfg.rollout_length
        assert env.vocab_size == model.vocab_size
        chunks, rest = divmod(cfg.rollout_length, model.mamba_chunk_size)
        assert chunks >= 2 and rest == 0
        assert env.delay > model.mamba_d_conv - 1  # beyond its reach
        assert model.layer_types.count("attention") == 1
    fns = make_ppo(cfg)  # the tiny one
    assert cli._snapshot_fits(fns, cfg)  # the CPU reports no capacity
    algo, base = cli.PRESETS["ppo-granite-recall"]
    cfg = PPOConfig(**dict(base, num_devices=1))
    fns = make_ppo(cfg)
    state = jax.eval_shape(fns.init, jax.random.PRNGKey(0))
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(state.params))
    assert n_params == 772_162_497
    core = state.carry["core"]
    assert core["state"].shape == (32, 9, 64, 64, 128)
    assert core["conv"].shape == (32, 9, 3, 4352)
    assert core["k"].shape == core["v"].shape == (32, 1, 512, 8, 64)
    assert state.obs.shape == (32,)
    chip = types.SimpleNamespace(
        memory_stats=lambda: {"bytes_limit": int(15.75 * 2**30)}
    )
    monkeypatch.setattr(jax, "local_devices", lambda *a, **k: [chip])
    assert not cli._snapshot_fits(fns, cfg)


def test_the_xing_presets_resolve_and_need_no_rollback_copy(monkeypatch):
    """Both Xing presets are PPO configs whose env and model agree on
    the vocabulary; one episode is one rollout; the cell holds one dense
    and four expert layers of 8 experts each with the published widths,
    four residual streams and 20 Sinkhorn iterations; its carry is the
    one cache of latents with the env axis first; and its train state
    (759 M parameters with Adam's moments, 8.5 GiB) does not fit a 16 GB
    chip beside a rollback copy of itself."""
    import types

    import jax

    from actor_critic_algs_on_tensorflow_tpu.algos.ppo import (
        PPOConfig,
        make_ppo,
    )

    for name in ("ppo-xing-recall", "ppo-xing-tiny"):
        algo, base = cli.PRESETS[name]
        cfg = PPOConfig(**dict(base, num_devices=1))
        env, model = cfg.env_params, cfg.seq_model
        assert algo == "ppo" and cfg.torso == "xing"
        assert cfg.recurrent and env.episode_length == cfg.rollout_length
        assert env.vocab_size == model.vocab_size
        assert model.hc_mult == 4 and model.q_lora_rank is not None
        assert [model.is_expert_layer(i)
                for i in range(model.num_hidden_layers)] == (
            [False] + [True] * (model.num_hidden_layers - 1))
    fns = make_ppo(cfg)  # the tiny one
    assert cli._snapshot_fits(fns, cfg)  # the CPU reports no capacity
    algo, base = cli.PRESETS["ppo-xing-recall"]
    cfg = PPOConfig(**dict(base, num_devices=1))
    assert (cfg.num_envs, cfg.rollout_length, cfg.num_minibatches) == (
        128, 256, 8)
    assert cfg.seq_model.hc_sinkhorn_iters == 20
    assert cfg.seq_model.hidden_size == 3584
    fns = make_ppo(cfg)
    state = jax.eval_shape(fns.init, jax.random.PRNGKey(0))
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(state.params))
    assert n_params == 759_350_031
    assert state.carry["core"]["layers"].shape == (128, 5, 256, 576)
    assert state.obs.shape == (128,)
    chip = types.SimpleNamespace(
        memory_stats=lambda: {"bytes_limit": int(15.75 * 2**30)}
    )
    monkeypatch.setattr(jax, "local_devices", lambda *a, **k: [chip])
    assert not cli._snapshot_fits(fns, cfg)


def test_the_lfm2_presets_resolve_and_need_no_rollback_copy(monkeypatch):
    """Both LFM2 presets are PPO configs whose env and model agree on
    the vocabulary; one episode is one rollout, longer than the
    convolutions' reach; the cell holds published layer 0 and layers
    2-5 (conv, attention, three conv), the first dense and four expert
    layers of 8 of 64 experts; its carry is the convolutions' float32
    tails and one attention layer's cache, the env axis first; and its
    train state (469 M parameters with Adam's moments, 7.0 GiB) does not
    fit a 16 GB chip beside a rollback copy of itself."""
    import types

    import jax

    from actor_critic_algs_on_tensorflow_tpu.algos.ppo import (
        PPOConfig,
        make_ppo,
    )

    for name in ("ppo-lfm2-recall", "ppo-lfm2-tiny"):
        algo, base = cli.PRESETS[name]
        cfg = PPOConfig(**dict(base, num_devices=1))
        env, model = cfg.env_params, cfg.seq_model
        assert algo == "ppo" and cfg.torso == "lfm2"
        assert cfg.recurrent and env.episode_length == cfg.rollout_length
        assert env.vocab_size == model.vocab_size
        assert env.delay > model.conv_L_cache - 1  # beyond the taps' reach
        assert model.layer_types == ("conv", "full_attention", "conv",
                                     "conv", "conv")
        assert [model.is_expert_layer(i) for i in range(5)] == (
            [False] + [True] * 4)
    fns = make_ppo(cfg)  # the tiny one
    assert cli._snapshot_fits(fns, cfg)  # the CPU reports no capacity
    algo, base = cli.PRESETS["ppo-lfm2-recall"]
    cfg = PPOConfig(**dict(base, num_devices=1))
    assert (cfg.num_envs, cfg.rollout_length, cfg.num_minibatches) == (
        128, 512, 8)
    assert cfg.seq_model.hidden_size == 2048
    assert cfg.seq_model.num_experts == 64
    assert cfg.seq_model.experts_held == 8
    fns = make_ppo(cfg)
    state = jax.eval_shape(fns.init, jax.random.PRNGKey(0))
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(state.params))
    assert n_params == 469_287_297
    core = state.carry["core"]
    assert core["conv"].shape == (128, 4, 2, 2048)
    assert core["conv"].dtype == jax.numpy.float32
    assert core["k"].shape == core["v"].shape == (128, 1, 512, 8, 64)
    assert core["k"].dtype == jax.numpy.bfloat16
    assert state.obs.shape == (128,)
    chip = types.SimpleNamespace(
        memory_stats=lambda: {"bytes_limit": int(15.75 * 2**30)}
    )
    monkeypatch.setattr(jax, "local_devices", lambda *a, **k: [chip])
    assert not cli._snapshot_fits(fns, cfg)
