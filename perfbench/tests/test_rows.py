"""The arithmetic of a window read row by row: one pause of the run
loop moves the whole-window quotient by as much as a bound allows a
cell's runs to spread, and leaves the median-based rate where it was;
what the median leaves out is what `async_pause_share` reads."""

import types

import pytest

from perfbench.harness import driver, rows

ROW_S, STEPS = 0.30958, 10 * 16384  # impala-pong on the chip


def _times(n_rows: int, pauses: dict) -> list:
    t, out = 100.0, [100.0]
    for i in range(n_rows - 1):
        t += ROW_S + pauses.get(i, 0.0)
        out.append(t)
    return out


@pytest.mark.parametrize("pauses", [
    {}, {7: 0.076}, {7: 0.076, 31: 0.076}, {0: 0.076, 53: 0.5},
])
def test_a_pause_does_not_move_the_steady_rate(pauses):
    times = _times(55, pauses)
    assert rows.steady_rate(times, STEPS) == pytest.approx(
        STEPS / ROW_S, rel=1e-9
    )
    whole = 54 * STEPS / (times[-1] - times[0])
    lost = sum(pauses.values()) / (times[-1] - times[0])
    assert whole == pytest.approx(STEPS / ROW_S * (1 - lost), rel=1e-9)
    assert rows.pause_share(times) == pytest.approx(100 * lost, abs=1e-9)


def test_one_pause_is_the_share_the_refusal_saw():
    """76 ms in a 17 s window: 0.45 %, against the 0.5 % a 1 % bound
    allows a new cell's runs to spread (BENCHMARK_REFUSED, PR 23)."""
    assert rows.pause_share(_times(55, {20: 0.076})) == pytest.approx(
        0.45, abs=0.01
    )


def test_a_slowdown_of_every_interval_moves_the_steady_rate():
    slow = [100.0 + i * ROW_S * 1.02 for i in range(55)]
    assert rows.steady_rate(slow, STEPS) == pytest.approx(
        STEPS / ROW_S / 1.02
    )
    assert rows.pause_share(slow) == pytest.approx(0.0, abs=1e-9)


def test_the_rule_reads_the_rows_and_returns_nothing_without_them():
    read = driver.load_rule("row_pause_share")
    ctx = types.SimpleNamespace(row_times_s=_times(10, {3: ROW_S}))
    assert read(ctx) == pytest.approx(10.0)
    assert read(types.SimpleNamespace(row_times_s=[])) is None
    assert read(types.SimpleNamespace(row_times_s=[1.0, 2.0])) is None
