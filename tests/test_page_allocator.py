"""serve/llm/pages.py alone: no model, no engine, no compiled program.
Every case here is a decision about where a sequence's cached tokens
live that the engine's tests only see through a whole model."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.attention import LayerCache, PagedKV, SlotState
from ray_tpu.serve.llm.pages import PageAllocator

PAGED = LayerCache(PagedKV, ((2, 128), (2, 128)), (jnp.bfloat16,) * 2)
BY_SLOT = LayerCache(SlotState, ((4, 16), (3, 8)),
                     (jnp.float32, jnp.bfloat16), by_slot=True)


def _alloc(spec=(PAGED, PAGED), max_slots=3, max_seq_len=64, page_size=8,
           pool_tokens=96):
    return PageAllocator(spec, max_slots, max_seq_len, page_size,
                         pool_tokens)


def _prefix(a, pid=0, n_tokens=20):
    """A prefix pinned as the engine registers one."""
    assert a.pin_prefix(pid, n_tokens)
    scratch = a.rows()[a.scratch_slot].copy()
    a.reset_scratch()
    return scratch


def test_sizing_follows_the_spec_and_the_four_numbers():
    a = _alloc()
    assert (a.n_slots, a.scratch_slot) == (4, 3)
    assert (a.pages_per_slot, a.n_pages, a.trash_page) == (8, 12, 12)
    assert a.n_flat == 13 * 8
    assert a.kv_bytes_per_token == 2 * (2 * 2 * 128 * 2)
    assert (a.state_bytes_per_slot, a.n_state_layers, a.len_layer) \
        == (0, 0, 0)
    assert a.rows().shape == (4, 8) and (a.rows() == 12).all()
    # no pool budget: every slot can reach max_seq_len; a budget is
    # rounded up to whole pages
    assert _alloc(pool_tokens=0).n_pages == 3 * 64 // 8
    assert _alloc(pool_tokens=17).n_pages == 3
    assert _alloc(max_seq_len=60).pages_per_slot == 8


def test_pools_of_a_paged_and_a_by_slot_layer():
    a = _alloc(spec=(BY_SLOT, PAGED))
    pools = jax.eval_shape(a.new_pools)
    assert [[(x.shape, x.dtype) for x in layer] for layer in pools] == [
        [((4, 4, 16), jnp.float32), ((4, 3, 8), jnp.bfloat16)],
        [((104, 2, 128), jnp.bfloat16)] * 2]
    assert a.kv_bytes_per_token == 2 * 2 * 128 * 2
    assert a.state_bytes_per_slot == 4 * 16 * 4 + 3 * 8 * 2
    assert (a.n_state_layers, a.len_layer) == (1, 1)


@pytest.mark.parametrize("kw, message", [
    (dict(page_size=0), "kv_page_size must be > 0, got 0"),
    (dict(page_size=-8), "kv_page_size must be > 0, got -8"),
    (dict(spec=(BY_SLOT,)), "keeps per-slot state and none pages")])
def test_what_cannot_be_sized_is_refused(kw, message):
    with pytest.raises(ValueError, match=message):
        _alloc(**kw)


def test_per_slot_state_refuses_sharing_and_rollback_once():
    assert _alloc().refuses("share") is None
    assert _alloc().refuses("roll_back") is None
    a = _alloc(spec=(BY_SLOT, PAGED))
    assert a.refuses("share") == (
        "this model keeps per-slot recurrent state, whose prefix would "
        "be a state snapshot: not supported")
    assert a.refuses("roll_back") == (
        "this model keeps per-slot recurrent state, which cannot be "
        "rolled back: not supported")


def test_reserve_then_release_restores_the_free_list_in_order():
    a = _alloc()
    before = list(a._free)
    # a stack: the last pages of the list, last first
    assert a.reserve(1, 5 + 14) == ([11, 10, 9], 0, None)
    assert a.rows()[1].tolist() == [11, 10, 9] + [12] * 5
    assert a.reserve(0, 8) == ([8], 0, None)
    a.release(1)
    assert a._free == before[:8] + [11, 10, 9]
    a.release(0)
    assert a._free == before[:8] + [11, 10, 9, 8]
    assert sorted(a._free) == before
    a.release(0)    # a slot that holds nothing: nothing happens
    assert sorted(a._free) == before


def test_a_released_row_is_all_trash_and_restarts_at_zero():
    a = _alloc()
    a.reserve(2, 30)
    assert a.take_length_edits().tolist() == [-1, -1, 0, -1]
    a.set_length(2, 17)
    a.release(2)
    assert (a.rows()[2] == a.trash_page).all()
    assert dict(a.dispatched_lengths) == {}
    # the row's length goes to 0 with the row, in the next program
    assert a.take_length_edits().tolist() == [-1, -1, 0, -1]


def test_length_edits_are_handed_out_once():
    a = _alloc()
    assert (a.take_length_edits() == -1).all()
    a.reserve(0, 9)
    a.reserve(2, 9)
    first = a.take_length_edits()
    assert first.dtype == np.int32 and first.tolist() == [0, -1, 0, -1]
    assert (a.take_length_edits() == -1).all()


def test_a_prefix_is_shared_by_reference_and_survives_its_adopter():
    a = _alloc()
    scratch = _prefix(a, 0, 20)            # two pages and a half
    assert scratch.tolist() == [11, 10, 9] + [12] * 5
    assert (a.rows()[a.scratch_slot] == 12).all()
    assert a.kv_pages()["pinned_prefix"] == 3
    # the two full pages by reference, the partial one as ONE copy onto
    # the adopter's own first page, the restart behind the prefix
    assert a.reserve(1, 20 + 7 + 10, prefix=0) \
        == ([11, 10, 8, 7, 6], 20, (9, 8))
    assert a.take_length_edits().tolist() == [-1, 20, -1, 0]
    assert dict(a.dispatched_lengths) == {1: 20}
    a.release(1)
    assert a._free == list(range(6)) + [8, 7, 6]
    assert a.kv_pages()["pinned_prefix"] == 3
    assert a.reserve(2, 20 + 3, prefix=0) == ([11, 10, 6], 20, (9, 6))
    a.release(2)
    a.unpin_prefix(0)
    assert sorted(a._free) == list(range(12))
    assert a.kv_pages()["pinned_prefix"] == 0


def test_a_prefix_that_ends_on_a_page_boundary_needs_no_copy():
    a = _alloc()
    _prefix(a, 0, 16)
    assert a.reserve(0, 16 + 4, prefix=0) == ([11, 10, 9], 16, None)


def test_a_reservation_taken_back_leaves_no_page_behind():
    """The engine's copy of the partial page failed: release."""
    a = _alloc()
    _prefix(a, 0, 20)
    free = list(a._free)
    pages, _restart, copy = a.reserve(1, 40, prefix=0)
    assert copy == (9, 8) and len(a._free) == len(free) - 3
    a.release(1)
    # as the engine's own clean-up put them back: extend(), in order
    assert a._free == free[:6] + pages[2:] and len(free) == 9
    assert (a.rows()[1] == a.trash_page).all()
    assert dict(a.dispatched_lengths) == {}
    assert a.kv_pages()["pinned_prefix"] == 3


def test_what_can_never_be_admitted_with_and_without_pins():
    a = _alloc()
    assert a.unservable(96) is None
    assert a.unservable(97) == (
        "request needs 13 KV pages; pool has 12 total — it could never "
        "be admitted")
    _prefix(a, 0, 20)
    assert a.unservable(9 * 8) is None
    assert a.unservable(9 * 8 + 1) == (
        "request needs 10 exclusive KV pages but only 9 can ever be free "
        "(3 pinned by prefixes)")
    # an adopter needs its own pages only: 2 of its 12 are shared
    assert a.unservable(11 * 8, prefix=0) is None
    assert a.unservable(11 * 8 + 1, prefix=0) == (
        "request needs 10 exclusive KV pages but only 9 can ever be free "
        "(3 pinned by prefixes)")
    # submit()'s question is the pool's size alone
    assert a.unservable(96, pins=False) is None
    assert "pool has 12 total" in a.unservable(97, pins=False)


def test_exhaustion_answers_no_pages_and_changes_nothing():
    a = _alloc(pool_tokens=32)
    a.reserve(0, 24)
    a.take_length_edits()
    free, table = list(a._free), a.rows().copy()
    assert a.reserve(1, 16) is None
    assert not a.pin_prefix(0, 16)
    assert a._free == free and (a.rows() == table).all()
    assert (a.take_length_edits() == -1).all()
    assert dict(a.dispatched_lengths) == {0: 0}
    assert a.kv_pages()["peak_in_use"] == 3
    a.release(0)
    assert a.reserve(1, 16)[0] == [1, 2]


@pytest.mark.parametrize("held, new, window", [
    ({}, 1, 1), ({0: 7}, 1, 1), ({0: 8}, 1, 2), ({0: 8, 1: 16}, 1, 4),
    ({0: 30}, 1, 4), ({0: 31}, 1, 4), ({0: 32}, 1, 0), ({0: 30}, 4, 0),
    ({2: 63}, 1, 0)])
def test_the_window_is_a_power_of_two_over_every_slot_that_holds_pages(
        held, new, window):
    a = _alloc(pool_tokens=0)
    for slot, n in held.items():
        # a slot that holds pages and is in nobody's active set: one
        # that is still chunk-prefilling
        a.reserve(slot, 64)
        a.set_length(slot, n)
    assert a.decode_window(new) == window
    full = a.rows()
    assert a.rows(window).shape == a.rows_shape(window) == (4, window or 8)
    assert np.shares_memory(a.rows(window), full)
    assert a.narrow(full, window).shape == (4, window or 8)
    assert a.narrow(full, 8) is full and a.narrow(full, 9) is full
    in_window, live = a.decode_pages(window, new)
    assert in_window == 4 * (window or 8)
    assert live == sum(-(-(n + new) // 8) for n in held.values())


def test_lengths_follow_dispatches_and_a_missing_slot_raises():
    a = _alloc()
    a.reserve(0, 30)
    a.reserve(2, 30)
    a.set_length(0, 11)
    a.advance([0, 2], 3)
    assert dict(a.dispatched_lengths) == {0: 14, 2: 3}
    a.set_length(1, 5)      # holds no pages: keeps no length
    assert 1 not in a.dispatched_lengths
    with pytest.raises(KeyError):
        a.advance([1], 1)
    with pytest.raises(TypeError):
        a.dispatched_lengths[0] = 0     # a view, for reading


def test_the_peak_and_the_kv_pages_numbers():
    a = _alloc()
    assert a.kv_pages() == {"page_size": 8, "total": 12, "free": 12,
                         "in_use": 0, "pinned_prefix": 0, "peak_in_use": 0}
    assert a.utilization() == 0.0
    a.reserve(0, 40)
    a.reserve(1, 9)
    a.release(0)
    assert a.kv_pages() == {"page_size": 8, "total": 12, "free": 10,
                         "in_use": 2, "pinned_prefix": 0, "peak_in_use": 7}
    assert a.utilization() == 2 / 12


def test_entries_follow_the_spec_a_layer():
    a = _alloc(spec=(BY_SLOT, PAGED), max_seq_len=16, pool_tokens=16)
    pools = jax.eval_shape(a.new_pools)
    table, lengths = a.rows(), np.zeros((3,), np.int32)
    state, paged = a.entries(pools, table, lengths, slots=None,
                             n_new=lengths)
    assert isinstance(state, SlotState) and isinstance(paged, PagedKV)
    assert paged.page_table is table and paged.page_size == 8
    assert state.slots is None and state.n_new is lengths
    # a page copy is a prefix's, so of paged layers only (`refuses`)
    a = _alloc(max_seq_len=16, pool_tokens=16)
    copied = jax.eval_shape(a.copy_page, jax.eval_shape(a.new_pools), 0, 1)
    assert [[x.shape for x in layer] for layer in copied] \
        == [[(24, 2, 128)] * 2] * 2
