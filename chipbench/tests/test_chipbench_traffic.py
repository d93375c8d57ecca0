"""The traffic generator: seeds reorder a fixed amount of work."""
import numpy as np

from chipbench import traffic

LIVE = {'rate_per_s': 50.0,
        'length_s': {'mean': 3.0, 'sigma': 0.7, 'min': 1.0, 'max': 15.0}}
BACKLOG = {'length_s': {'median': 15.0, 'sigma': 0.6, 'min': 5.0,
                        'max': 60.0}}


def test_lengths_are_clipped_lognormal_quantiles():
    s = traffic.lengths_s(LIVE['length_s'], 20000)
    assert s.min() >= 1.0 and s.max() <= 15.0
    assert abs(s.mean() - 3.0) < 0.1
    assert np.array_equal(s, np.sort(s))


def test_live_seeds_share_lengths_and_gaps_in_another_order():
    a = traffic.live(LIVE, seed=1, n_features=3, horizon_s=30, frame_s=0.01)
    b = traffic.live(LIVE, seed=2**40 + 3, n_features=3, horizon_s=30,
                     frame_s=0.01)
    assert not np.array_equal(a.length, b.length)
    # streams wholly after the clock starts come from one multiset; the
    # counts of arrivals in the horizon differ by edge effects only
    assert abs(len(a) - len(b)) <= 0.02 * len(a)
    assert abs(a.length.mean() - b.length.mean()) < 0.03 * a.length.mean()
    assert (np.diff(a.arrival_s) >= 0).all()


def test_live_streams_before_the_clock_enter_in_progress():
    s = traffic.live(LIVE, seed=4, n_features=3, horizon_s=10, frame_s=0.01)
    assert (s.arrival_s >= 0).all()
    early = s.arrival_s < 0.01
    # the rate's steady concurrency, rate x mean length, is there at once
    assert abs(early.sum() - 50 * 3.0) < 0.3 * 50 * 3.0
    assert (s.length >= 1).all() and (s.length <= 1500).all()
    f = s.frames(0)
    assert f.shape == (s.length[0], 3) and f.dtype == np.float32


def test_backlog_first_grid_enters_at_a_residual_length():
    s = traffic.backlog(BACKLOG, seed=9, n_features=3, slots=256,
                        n_streams=2000, frame_s=0.01)
    first, rest = s.length[:256], s.length[256:]
    assert first.mean() < rest.mean()
    assert rest.min() >= 500 and rest.max() <= 6000
    assert np.isnan(s.arrival_s).all()
    t = traffic.backlog(BACKLOG, seed=10, n_features=3, slots=256,
                        n_streams=2000, frame_s=0.01)
    assert np.array_equal(np.sort(s.length[256:512]),
                          np.sort(t.length[256:512]))
