"""The loop's arithmetic on recorded block times: the rate and `step_ms` are
all the work over all the time of the window, so a late block moves them by
its share of the window, and nothing is counted over the nominal length."""
import pytest

from benchmark import loop

STEP = 0.3114          # seconds; an ERNIE step
TOKENS = 64 * 512


def _readings(n=100, late=None):
    r = [STEP * (1 + 1e-4 * ((i * 7) % 5 - 2)) for i in range(n)]
    if late is not None:
        r[late] += STEP             # one block a whole step late
    return r


def test_rates_are_the_window_s_work_over_its_wall_time():
    r = _readings()
    out = loop.reduce_window(r, 1, sum(r), TOKENS, 1)
    assert out["step_ms"] == pytest.approx(sum(r) / 100 * 1e3)
    assert out["rate_per_chip"] == pytest.approx(100 * TOKENS / sum(r))
    assert out["step_ms_median"] == pytest.approx(STEP * 1e3, rel=1e-4)
    assert out["readings"] == 100 and out["steps"] == 100
    four = loop.reduce_window(r, 1, sum(r), 4 * TOKENS, 4)
    assert four["rate_per_chip"] == pytest.approx(out["rate_per_chip"])


@pytest.mark.parametrize("late", [0, 37, 99])
def test_one_late_block_moves_the_rate_by_its_share_of_the_window(late):
    clean, stalled = _readings(), _readings(late=late)
    a = loop.reduce_window(clean, 1, sum(clean), TOKENS, 1)
    b = loop.reduce_window(stalled, 1, sum(stalled), TOKENS, 1)
    share = STEP / sum(stalled)          # the stall's share of the window
    assert b["rate_per_chip"] == pytest.approx(
        a["rate_per_chip"] * (1 - share), rel=1e-6)
    assert b["step_ms"] == pytest.approx(a["step_ms"] / (1 - share), rel=1e-6)
    # the steady diagnostics say what it was: the median stays, the stall
    # share reads the stall
    assert b["step_ms_median"] == pytest.approx(a["step_ms_median"], rel=1e-4)
    assert loop.stall_share(clean, 1, sum(clean)) == pytest.approx(0, abs=0.02)
    assert loop.stall_share(stalled, 1, sum(stalled)) == pytest.approx(
        100 * share, rel=0.02)


def test_nothing_is_counted_over_the_nominal_window():
    """PR 22's fault: steps over `--seconds` gains or loses a whole step at
    the window's edge. 103 blocks that overrun a 32 s window by most of a
    step read the same step_ms as 102 that stop short of it."""
    short, long_ = [STEP] * 102, [STEP] * 103
    a = loop.reduce_window(short, 1, sum(short), TOKENS, 1)
    b = loop.reduce_window(long_, 1, sum(long_), TOKENS, 1)
    assert a["rate_per_chip"] == pytest.approx(b["rate_per_chip"])
    assert 103 * TOKENS / 32.0 / (102 * TOKENS / 32.0) > 1.009


def test_p90_needs_ten_late_blocks_in_a_hundred_to_move_fully():
    r = _readings()
    assert loop.p90(r) == pytest.approx(STEP, rel=1e-3)
    for i in range(0, 100, 9):          # 12 late blocks
        r[i] += 0.05
    assert loop.p90(r) == pytest.approx(STEP + 0.05, rel=1e-3)
    out = loop.reduce_window(r, 1, sum(r), TOKENS, 1)
    assert out["step_ms_p90"] == pytest.approx((STEP + 0.05) * 1e3, rel=1e-3)
    assert out["step_ms"] == pytest.approx((STEP + 0.12 * 0.05) * 1e3,
                                           rel=1e-3)


def test_host_time_between_blocks_is_in_the_rate():
    # 24-step blocks of a 12.9 ms step; 5% of the wall lies between blocks
    readings = [0.0129] * 50
    wall = 50 * 24 * 0.0129 / 0.95
    assert loop.stall_share(readings, 24, wall) == pytest.approx(5.0)
    out = loop.reduce_window(readings, 24, wall, 4096, 1)
    assert out["steps"] == 1200
    assert out["rate_per_chip"] == pytest.approx(0.95 * 4096 / 0.0129)
    assert out["step_ms"] == pytest.approx(12.9 / 0.95)
    assert out["step_ms_median"] == pytest.approx(12.9)


def test_no_reading_is_an_error():
    with pytest.raises(ValueError):
        loop.reduce_window([], 1, 1.0, TOKENS, 1)
    assert loop.p90([0.5]) == 0.5
