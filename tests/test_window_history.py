"""AccessHistory ring buffer + adaptive prefetch window (Alg. 2) properties."""

import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core.history import AccessHistory
from repro.core.window import (PrefetchWindow, _round_up_pow2_jax,
                               init_window_state, next_window_size,
                               note_prefetch_hits, round_up_pow2)


@given(st.lists(st.integers(0, 1 << 20), min_size=1, max_size=100))
def test_history_window_returns_newest_first(pages):
    h = AccessHistory(16)
    deltas = []
    last = None
    for p in pages:
        deltas.append(0 if last is None else p - last)
        last = p
        h.push(p)
    got = h.window(min(16, len(pages)))
    expect = list(reversed(deltas))[: min(16, len(pages))]
    assert list(got) == expect


def test_history_requires_pow2():
    with pytest.raises(ValueError):
        AccessHistory(12)


# No per-example deadline: the jitted twin compiles on the first example,
# which can exceed hypothesis's 200 ms default on a loaded CPU.
@settings(deadline=None)
@given(st.integers(1, 1 << 20))
def test_round_up_pow2(x):
    p = round_up_pow2(x)
    assert p >= x and p < 2 * x or (x == 1 and p == 1)
    assert p & (p - 1) == 0
    import jax.numpy as jnp
    assert int(_round_up_pow2_jax(jnp.int32(x))) == p


class TestPrefetchWindow:
    def test_grows_with_hits_capped(self):
        w = PrefetchWindow(pw_max=8)
        for hits in (1, 3, 9, 20):
            for _ in range(hits):
                w.note_prefetch_hit()
            pw = w.next_size(follows_trend=True)
            assert pw == min(round_up_pow2(hits + 1), 8)

    def test_zero_hits_follows_trend_keeps_minimum(self):
        w = PrefetchWindow(pw_max=8)
        assert w.next_size(follows_trend=True) == 1

    def test_zero_hits_off_trend_suspends(self):
        w = PrefetchWindow(pw_max=8)
        assert w.next_size(follows_trend=False) == 0

    def test_smooth_shrink(self):
        """Alg. 2 line 13-14: never collapse below half the previous window."""
        w = PrefetchWindow(pw_max=8)
        for _ in range(10):
            w.note_prefetch_hit()
        assert w.next_size(True) == 8
        w.note_prefetch_hit()          # only 1 hit -> would be 2, floor 4
        assert w.next_size(True) == 4

    @given(st.lists(st.tuples(st.integers(0, 12), st.booleans()),
                    min_size=1, max_size=50))
    def test_window_bounded(self, events):
        w = PrefetchWindow(pw_max=8)
        for hits, follows in events:
            for _ in range(hits):
                w.note_prefetch_hit()
            pw = w.next_size(follows)
            assert 0 <= pw <= 8


class TestTwinEquivalence:
    """``PrefetchWindow.next_size`` and the JAX ``next_window_size`` are
    twins: identical window sequence and identical carried state over any
    hit/trend history — including the shrink-smoothly branch
    (``pw < pw_prev // 2``, Alg. 2 line 13-14) that spot checks only graze.
    """

    @staticmethod
    def _step_both(ref, state, hits, follows, pw_max):
        import jax.numpy as jnp
        for _ in range(hits):
            ref.note_prefetch_hit()
        state = note_prefetch_hits(state, jnp.int32(hits))
        state, pw_j = next_window_size(state, jnp.asarray(follows), pw_max)
        pw_r = ref.next_size(follows)
        assert int(pw_j) == pw_r
        assert int(state["pw_prev"]) == ref.pw_prev
        assert int(state["c_hit"]) == ref.c_hit == 0
        return state, pw_r

    # No per-example deadline: up to 60 jitted window steps per example,
    # and the first example of each pw_max pays its compiles (hundreds of
    # ms on a loaded CPU), which hypothesis reports as flaky timing rather
    # than a property failure.
    @settings(deadline=None)
    @given(st.lists(st.tuples(st.integers(0, 20), st.booleans()),
                    min_size=1, max_size=60),
           st.sampled_from([4, 8, 16, 64]))
    def test_twins_agree_on_random_histories(self, events, pw_max):
        ref = PrefetchWindow(pw_max=pw_max)
        state = init_window_state()
        for hits, follows in events:
            state, _ = self._step_both(ref, state, hits, follows, pw_max)

    @settings(deadline=None)      # jitted twin: same reason as above
    @given(st.integers(7, 40), st.integers(1, 2), st.booleans())
    def test_twins_agree_through_the_shrink_branch(self, big, small,
                                                   follows):
        """Grow to pw_prev == pw_max, then starve: c_hit=1 would collapse to
        2 but must floor at pw_prev // 2 = 4 in BOTH twins (c_hit=2 sits
        exactly on the boundary and must NOT clamp)."""
        ref = PrefetchWindow(pw_max=8)
        state = init_window_state()
        # big >= 7 -> round_up_pow2(big + 1) >= 8 -> window pegged at cap
        state, pw = self._step_both(ref, state, big, True, 8)
        assert pw == 8
        state, pw = self._step_both(ref, state, small, follows, 8)
        # c_hit=1: pow2(2)=2 floored at 4; c_hit=2: pow2(3)=4, boundary,
        # no clamp — both land on 4 through *different* branches
        assert pw == 4
        # and the floor keeps halving smoothly, never cliff-dropping
        state, pw = self._step_both(ref, state, 1, follows, 8)
        assert pw == 2
