"""The runners' functions at a tiny size on the CPU: set-up (init from
the seed, the reference check), a window of whole iterations or
learner batches, and the checks that decide ``correct``."""

import jax
import pytest

from perfbench.harness import checks, driver, rows

from perfbench.tests.helpers import no_span, nothing, tiny_cell


@pytest.mark.parametrize("name,program", [
    ("ppo-pong", dict(num_envs=8)),
    ("ppo-breakout", dict(num_envs=16)),
])
def test_ppo_runner_tiny(name, program):
    cell = tiny_cell(name, **program)
    runner = driver.load_runner("ppo")(cell, seed=3)
    assert runner.setup() == {"placement": True}
    window = runner.measure(0.0, nothing, nothing, no_span)
    # A window shorter than an iteration still holds two whole ones.
    assert window["attempted"] == window["iterations"] == 2
    assert window["failed"] == 0
    assert all(window["checks"].values()), window["checks"]
    # The rate is one iteration's env steps over the median time
    # between two iterations' ends, the window's opening being the
    # first; the whole-window quotient stays beside it.
    ends = window["row_times_s"]
    assert len(ends) == 3 and ends == sorted(ends)
    steps = runner.cfg.num_envs * 8
    assert window["end_to_end"]["env_steps_per_s_per_chip"] == (
        rows.steady_rate(ends, steps)
    )
    assert window["whole_window_env_steps_per_s_per_chip"] == pytest.approx(
        2 * steps / (ends[-1] - ends[0])
    )
    per_it = cell.traffic["expect"]["optimizer_updates_per_iteration"]
    assert checks.optimizer_count(runner.state.opt_state) == 3 * per_it
    assert runner.verify() == {"reference_model_and_ops": True}, runner.report
    work = window["work_per_execution"]["^jit_local_iteration"]
    assert work["train_samples"] == runner.cfg.num_epochs * runner.cfg.num_envs * 8
    assert work["train_calls"] == per_it


def test_ppo_x4_shards_over_four_devices():
    cell = tiny_cell("ppo-pong-x4", num_envs=16)
    assert cell.chips == 4 and len(jax.devices()) >= 4
    runner = driver.load_runner("ppo")(cell, seed=0)
    setup = runner.setup()
    assert setup["placement"], "params replicated, env state sharded"
    shards = runner.state.obs.addressable_shards
    assert len(shards) == 4 and shards[0].data.shape[0] == 4
    window = runner.measure(0.0, nothing, nothing, no_span)
    assert all(window["checks"].values()), window["checks"]
    assert runner.verify() == {"reference_model_and_ops": True}, runner.report


def test_impala_runner_tiny():
    cell = tiny_cell("impala-pong", envs_per_actor=4)
    runner = driver.load_runner("impala")(cell, seed=1)
    assert runner.setup() == {}
    started = []
    window = runner.measure(
        1.5, lambda: started.append(1), nothing, no_span
    )
    assert started == [1]
    assert window["attempted"] >= 2 and window["failed"] == 0
    assert all(window["checks"].values()), window["checks"]
    rate = window["end_to_end"]["async_env_steps_per_s_per_chip"]
    per_row = 2 * 4 * 8  # log_interval x envs x rollout
    assert rate == rows.steady_rate(window["row_times_s"], per_row) > 0
    assert len(window["row_times_s"]) == window["readings"]
    assert window["whole_window_env_steps_per_s_per_chip"] == pytest.approx(
        window["attempted"] * 4 * 8 / window["elapsed_s"]
    )
    assert window["log_rows"] and "pipeline_stall_s" in window["log_rows"][0]
    assert runner.verify() == {"reference_learner_step": True}, runner.report


def test_short_optimizer_count_fails_the_check():
    """`correct` fails when the traffic file's epochs or minibatches
    are not what the program ran: 10 iterations of a stated 64 updates
    against a program that made 2 each."""
    assert checks.updates_consistent(2, 2 + 10 * 64, 10, 64)
    assert not checks.updates_consistent(2, 2 + 10 * 2, 10, 64)
    assert not checks.updates_consistent(0, 9, 10, 1)


def test_traffic_schedule_mismatch_is_caught_in_the_window():
    cell = tiny_cell("ppo-pong", num_envs=8)
    cell.traffic["expect"]["optimizer_updates_per_iteration"] = 64
    runner = driver.load_runner("ppo")(cell, seed=0)
    runner.setup()
    window = runner.measure(0.0, nothing, nothing, no_span)
    assert not window["checks"]["optimizer_updates"]
    assert not window["checks"]["program_runs_traffic_schedule"]


def test_fused_loss_terms_hold_the_programs_own_coefficients():
    """What the fused PPO iteration reports is held to the
    configuration's coefficients: the identity passes on a real
    iteration's metrics and fails for a doubled value coefficient, a
    dropped entropy term or a missing term."""
    cell = tiny_cell("ppo-pong", num_envs=8)
    runner = driver.load_runner("ppo")(cell, seed=5)
    runner.setup()
    window = runner.measure(0.0, nothing, nothing, no_span)
    assert window["checks"]["fused_loss_terms"]
    _, m = runner.fns.iteration(runner.state)
    m = {k: float(v) for k, v in jax.device_get(m).items()}
    hp = {"vf_coef": runner.cfg.vf_coef, "ent_coef": runner.cfg.ent_coef}
    assert checks.loss_terms_consistent(m, hp)
    assert not checks.loss_terms_consistent(m, dict(hp, vf_coef=1.0))
    assert not checks.loss_terms_consistent(m, dict(hp, ent_coef=0.0))
    assert not checks.loss_terms_consistent(
        {k: v for k, v in m.items() if k != "entropy"}, hp
    )
