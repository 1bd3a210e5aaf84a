"""Cache-policy properties (NAVIS window+frozen, LRU, CLOCK, LFU)."""
import jax
import jax.numpy as jnp
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import cache as C

KEY = jax.random.PRNGKey(0)
P_MAX = 256

# jitted once: op-by-op dispatch of the cache's many tiny lax ops floods
# the XLA:CPU JIT with one compiled program per op
_access = jax.jit(C.access, static_argnames="policy")
_invalidate = jax.jit(C.invalidate_page)


def _mk(policy, capacity=16):
    return C.init_cache(P_MAX, capacity, policy, KEY)


def _occupancy(st_):
    return int((st_.window_pages >= 0).sum() + (st_.frozen_pages >= 0).sum())


@pytest.mark.parametrize("policy", ["navis", "lru", "clock", "lfu"])
def test_hit_after_access(policy):
    st_ = _mk(policy)
    hit, st_ = _access(st_, jnp.int32(5), policy)
    assert not bool(hit)
    hit, st_ = _access(st_, jnp.int32(5), policy)
    assert bool(hit)


@pytest.mark.parametrize("policy", ["navis", "lru", "clock", "lfu"])
def test_capacity_never_exceeded(policy):
    st_ = _mk(policy, capacity=10)
    for p in range(40):
        _, st_ = _access(st_, jnp.int32(p % 23), policy)
    assert _occupancy(st_) <= 10


def test_none_policy_never_hits():
    st_ = _mk("none")
    for _ in range(3):
        hit, st_ = _access(st_, jnp.int32(1), "none")
        assert not bool(hit)


def test_navis_promotion_needs_two_window_hits():
    st_ = _mk("navis", capacity=20)          # window=2, frozen=18
    _, st_ = _access(st_, jnp.int32(7), "navis")   # miss -> window
    assert int(st_.status[7]) == 1           # IN_WINDOW
    _, st_ = _access(st_, jnp.int32(7), "navis")   # window hit -> promoted
    assert int(st_.status[7]) == 2           # IN_FROZEN
    slot = int(st_.slot_of[7])
    assert int(st_.frozen_pages[slot]) == 7


def test_navis_one_off_pages_never_pollute_frozen():
    st_ = _mk("navis", capacity=20)
    for p in range(50, 90):                  # one-off scan
        _, st_ = _access(st_, jnp.int32(p), "navis")
    assert int((st_.frozen_pages >= 0).sum()) == 0


def test_lru_evicts_oldest():
    st_ = _mk("lru", capacity=3)
    for p in (1, 2, 3):
        _, st_ = _access(st_, jnp.int32(p), "lru")
    _, st_ = _access(st_, jnp.int32(1), "lru")   # refresh 1
    _, st_ = _access(st_, jnp.int32(4), "lru")   # evicts 2 (oldest)
    hit, st_ = _access(st_, jnp.int32(2), "lru")
    assert not bool(hit)
    hit, st_ = _access(st_, jnp.int32(1), "lru")
    assert bool(hit)


def test_invalidate_page_drops_entry():
    st_ = _mk("navis", capacity=20)
    _, st_ = _access(st_, jnp.int32(9), "navis")
    st_ = _invalidate(st_, jnp.int32(9))
    assert int(st_.status[9]) == 0
    hit, st_ = _access(st_, jnp.int32(9), "navis")
    assert not bool(hit)


@settings(max_examples=10, deadline=None)
@given(policy=st.sampled_from(["navis", "lru", "clock", "lfu"]),
       seed=st.integers(0, 999))
def test_status_slot_consistency(policy, seed):
    """status/slot_of tables always agree with the region arrays."""
    st_ = _mk(policy, capacity=8)
    k = jax.random.PRNGKey(seed)
    pages = jax.random.randint(k, (60,), 0, 30)
    for p in pages:
        _, st_ = _access(st_, p.astype(jnp.int32), policy)
    status = jax.device_get(st_.status)
    slot_of = jax.device_get(st_.slot_of)
    window = jax.device_get(st_.window_pages)
    frozen = jax.device_get(st_.frozen_pages)
    for page in range(P_MAX):
        if status[page] == 1:
            assert window[slot_of[page]] == page
        elif status[page] == 2:
            assert frozen[slot_of[page]] == page
    for slot, page in enumerate(window):
        if page >= 0:
            assert status[page] == 1 and slot_of[page] == slot
    for slot, page in enumerate(frozen):
        if page >= 0:
            assert status[page] == 2 and slot_of[page] == slot
