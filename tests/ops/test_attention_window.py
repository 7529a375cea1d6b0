"""A window in `ops/attention`: query i sees key j iff ``0 <= i - j < window`` inside its document.

The dense mask (`make_attention_mask`: eager and sdpa), the block tables
(`document_block_pairs(window=)`: a key block further back than the window reaches is not
needed) and jax's splash kernel under a local in-block mask function on those tables — all three
against a mask written out, the kernel interpreted here on the CPU (values and programs, never a
time) at toy sizes. With no window, the tables' jaxpr is the commit before's (a hash), and the
paths that know no window refuse one loudly.
"""

import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dolomite_engine_tpu.enums import AttentionImplementation
from dolomite_engine_tpu.ops import attention as attention_ops
from dolomite_engine_tpu.ops.attention import (
    SPLASH_COUNTERS_BY_KIND,
    _document_block_tables,
    _pick_block,
    _repeat_kv,
    _tpu_splash_attention,
    attention,
    document_block_pairs,
    eager_attention,
    make_attention_mask,
    sdpa_attention,
    splash_block_counters,
    splash_block_counters_by_kind,
    window_block_reach,
)
from dolomite_engine_tpu.utils.telemetry import Telemetry, install_telemetry, uninstall_telemetry

from tests.ops.test_splash_block_tables import _ids, _launch_grids, _random_documents  # rows of documents, as the tables' own tests make them

SEQ, BLOCK = 640, 128  # `_pick_block(640)` is 128: five blocks a row


def _dense(ids: np.ndarray, window: int | None) -> np.ndarray:
    """[S, S], written out: key at or before the query, the same id, no padding, inside the window."""
    place = np.arange(len(ids))
    mask = (place[:, None] >= place[None, :]) & (ids[:, None] == ids[None, :]) & (ids != 0)[None, :]
    return mask if window is None else mask & (place[:, None] - place[None, :] < window)


# ---------------------------------------------------------------- the dense mask

@pytest.mark.parametrize("window", [1, 5, 64, 1000])
def test_the_dense_mask_is_the_band_inside_each_document(window):
    ids = np.stack([_ids([30, 50, 10], 96), _ids([96], 96)])
    mask = make_attention_mask(2, 96, 96, causal=True, segment_ids_q=jnp.asarray(ids), window=window)
    for row in range(2):
        np.testing.assert_array_equal(np.asarray(mask[row, 0]), _dense(ids[row], window))
    # a per-row frontier (continuous batching) keeps the band where the row's queries stand
    offsets = jnp.asarray([3, 40])
    mask = np.asarray(make_attention_mask(2, 4, 96, causal=True, query_offset=offsets, window=window))
    for row, offset in enumerate((3, 40)):
        for i in range(4):
            seen = np.flatnonzero(mask[row, 0, i])
            assert seen.max() == offset + i and seen.min() == max(0, offset + i - window + 1)
    with pytest.raises(NotImplementedError, match="without the causal mask"):
        make_attention_mask(1, 8, 8, causal=False, window=4)


@pytest.mark.parametrize("implementation", [AttentionImplementation.eager, AttentionImplementation.sdpa, AttentionImplementation.flash_attention_2])
def test_every_implementation_the_trainer_can_select_computes_the_same_window(implementation):
    """eager, sdpa and (off a TPU: sdpa again) flash_attention_2 against the softmax written out."""
    rng = np.random.RandomState(0)
    ids = jnp.asarray(np.stack([_ids([30, 50, 10], 96), _ids([96], 96)]))
    q, k, v = (jnp.asarray(rng.randn(2, 96, heads, 16), jnp.float32) for heads in (4, 2, 2))
    out = attention(q, k, v, implementation=implementation, segment_ids=ids, window=7)
    for row in range(2):
        mask = _dense(np.asarray(ids[row]), 7)
        for head in range(4):
            scores = np.asarray(q[row, :, head]) @ np.asarray(k[row, :, head // 2]).T * 16**-0.5
            scores = np.where(mask, scores, -np.inf)
            real = mask.any(axis=1)
            probs = np.exp(scores[real] - scores[real].max(axis=1, keepdims=True))
            expected = probs / probs.sum(axis=1, keepdims=True) @ np.asarray(v[row, :, head // 2])
            np.testing.assert_allclose(np.asarray(out[row, real, head]), expected, rtol=1e-4, atol=1e-5)
    whole = attention(q, k, v, implementation=implementation, segment_ids=ids)
    np.testing.assert_allclose(attention(q, k, v, implementation=implementation, segment_ids=ids, window=96), whole, rtol=1e-6, atol=1e-6)
    assert float(jnp.abs(out - whole).max()) > 1e-3


# ---------------------------------------------------------------- the tables, a pure function

def _needed_by_brute_force(ids: np.ndarray, block: int, window: int | None) -> np.ndarray:
    """[n, n]: whether some query of block i and some key of block j may attend each other."""
    n = len(ids) // block
    place = np.arange(len(ids))
    same = (ids[:, None] == ids[None, :]) & (place[:, None] >= place[None, :])
    if window is not None:
        same &= place[:, None] - place[None, :] < window
    return same.reshape(n, block, n, block).any(axis=(1, 3))


SEQ_T, BLOCK_T = 1024, 64  # sixteen blocks a row

ID_ROWS = {
    **{f"packed_seed{seed}": (_ids(_random_documents(seed, SEQ_T, 200.0), SEQ_T), True) for seed in range(4)},
    "one_document": (_ids([SEQ_T], SEQ_T), True),
    "every_token_its_own": (np.arange(1, SEQ_T + 1, dtype=np.int32), True),
    "tail_padding": (_ids(_random_documents(7, SEQ_T - 200, 200.0), SEQ_T), True),
    # ids in no order: the ranges are conservative, never wrong
    "ids_shuffled": (np.random.RandomState(3).permutation(40)[_ids(_random_documents(8, SEQ_T, 200.0), SEQ_T) % 40].astype(np.int32), False),
    "an_id_comes_back": (_ids([100, 300, 200], SEQ_T) % 3, False),
}
# windows under a block, of a block, of blocks and one, off every block, and longer than the row
WINDOWS = [1, 2, 33, 64, 65, 66, 200, 256, 257, 2000]


@pytest.mark.parametrize("window", WINDOWS)
@pytest.mark.parametrize("case", ID_ROWS)
def test_windowed_block_pairs_never_skip_a_needed_pair_and_are_exact_on_ordered_ids(case, window):
    ids, ordered = ID_ROWS[case]
    needed = np.asarray(document_block_pairs(jnp.asarray(ids)[None], BLOCK_T, window))[0]
    brute = _needed_by_brute_force(ids, BLOCK_T, window)
    assert not (brute & ~needed).any()  # a needed pair is never skipped
    assert not np.triu(needed, 1).any() and needed.diagonal().all()
    assert not (needed & ~np.asarray(document_block_pairs(jnp.asarray(ids)[None], BLOCK_T))[0]).any()  # a window only takes away
    if ordered:
        np.testing.assert_array_equal(needed, brute)


def test_the_reach_of_a_window_in_blocks():
    # the deployment's: 2048 keys at blocks of 512 reach four blocks back, five key blocks with the query's own
    assert window_block_reach(2048, 512) == 4 and window_block_reach(2049, 512) == 4 and window_block_reach(2050, 512) == 5
    assert (window_block_reach(1, 512), window_block_reach(2, 512), window_block_reach(513, 512), window_block_reach(514, 512)) == (0, 1, 1, 2)


# the jaxprs of the tables and of the counters on 2 rows of 1024 ids as the commit before this one (212dfd7) traced them
PARENT_JAXPRS = {"document_block_pairs": "57e438fd40828056", "splash_block_counters": "1a07bfc89d93761a"}


def test_no_window_leaves_the_tables_jaxpr_alone():
    ids = jax.ShapeDtypeStruct((2, SEQ_T), jnp.int32)
    digest = lambda fn: hashlib.sha256(str(jax.make_jaxpr(fn)(ids)).encode()).hexdigest()[:16]  # noqa: E731
    assert digest(lambda s: document_block_pairs(s, BLOCK_T)) == PARENT_JAXPRS["document_block_pairs"]
    assert digest(lambda s: document_block_pairs(s, BLOCK_T, None)) == PARENT_JAXPRS["document_block_pairs"]
    assert digest(lambda s: splash_block_counters(2, SEQ_T, s)) == PARENT_JAXPRS["splash_block_counters"]
    assert digest(lambda s: document_block_pairs(s, BLOCK_T, 200)) != PARENT_JAXPRS["document_block_pairs"]


# the gradient of the segmented call on 2 rows of 1024 (two blocks of 512 a row) as the commit
# before this one (aac3fe9) traced it: no window, and windows that reach both of a row's blocks
PARENT_GRADIENTS = {None: "e7cb68c746d8e6ab", 200: "ac172c352f59d8aa", 2000: "0fd794f6e3397908"}


@pytest.mark.parametrize("window", PARENT_GRADIENTS)
def test_a_call_whose_window_reaches_the_whole_row_or_that_has_none_is_the_parent_s_program(window):
    """Banded tables are built only where they are narrower than a row: everywhere else the
    three launches, their tables and everything around them are what they were."""
    q = jax.ShapeDtypeStruct((2, SEQ_T, 4, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((2, SEQ_T, 2, 128), jnp.bfloat16)
    ids = jax.ShapeDtypeStruct((2, SEQ_T), jnp.int32)
    grad = jax.grad(lambda q, k, v, s: _tpu_splash_attention(q, k, v, s, 128**-0.5, window=window).astype(jnp.float32).sum(), argnums=(0, 1, 2))
    assert hashlib.sha256(str(jax.make_jaxpr(grad)(q, kv, kv, ids)).encode()).hexdigest()[:16] == PARENT_GRADIENTS[window]


def test_counters_by_kind_count_each_kind_s_tables():
    rows = jnp.asarray(np.stack([ID_ROWS["packed_seed0"][0], ID_ROWS["one_document"][0]]))
    block = _pick_block(SEQ_T)  # 512: two blocks a row
    n = SEQ_T // block
    counted = jax.jit(lambda r: splash_block_counters_by_kind(2, SEQ_T, r, 300, window_layers=4, full_layers=1))(rows)
    assert set(counted) == set(SPLASH_COUNTERS_BY_KIND)
    windowed = sum(int(_needed_by_brute_force(np.asarray(row), block, 300).sum()) for row in rows)
    full = sum(int(_needed_by_brute_force(np.asarray(row), block, None).sum()) for row in rows)
    assert int(counted["splash_blocks_visited_window"]) == 4 * windowed and int(counted["splash_blocks_visited_full"]) == full
    assert int(counted["splash_blocks_causal"]) == 5 * 2 * n * (n + 1) // 2
    # at smaller blocks a window layer visits fewer blocks than a full one, and both fewer than the triangle
    fine = {w: int(document_block_pairs(rows, 64, w).sum()) for w in (130, None)}
    assert fine[130] < fine[None] <= 2 * 16 * 17 // 2
    # no segment ids: the static tables run the window's band; a length the kernel does not take: nothing
    static = splash_block_counters(2, 2048, None, window=600)  # blocks of 512: reach 2
    assert int(static["splash_blocks_visited"]) == 2 * (1 + 2 + 3 + 3) and int(static["splash_blocks_causal"]) == 2 * 10
    assert {k: int(v) for k, v in splash_block_counters_by_kind(2, 100, rows[:, :100], 30, 4, 1).items()} == dict.fromkeys(SPLASH_COUNTERS_BY_KIND, 0)


# ---------------------------------------------------------------- the kernel on the windowed tables

ROWS = {
    "crossing_blocks": [_ids([200, 250, 190], SEQ)],
    "one_document": [_ids([SEQ], SEQ)],
    "two_rows_and_padding": [_ids([128, 384, 100], SEQ), _ids([500, 40, 100], SEQ)],
}
# a window inside a block, one that reaches into the block before, a multiple of the block, one that is no multiple
KERNEL_WINDOWS = [40, 129, 256, 300]


@pytest.mark.parametrize("window", KERNEL_WINDOWS)
@pytest.mark.parametrize("rows", ROWS)
def test_splash_on_the_windowed_tables_is_sdpa_under_the_same_window(rows, window):
    """Output and the gradients of q, k, v of the segmented call under a window (the head shape
    of the family that has one, a quarter of its heads: 8 over 1 of 128), interpreted: `sdpa`'s
    with the same ids and window wherever a token is no padding — and not those without the
    window."""
    hq, hkv, d = 8, 1, 128
    segment_ids = jnp.asarray(np.stack(ROWS[rows]))
    batch = segment_ids.shape[0]
    rng = np.random.RandomState(len(rows) + window)
    q = jnp.asarray(rng.randn(batch, SEQ, hq, d), jnp.float32)
    k = jnp.asarray(rng.randn(batch, SEQ, hkv, d), jnp.float32)
    v = jnp.asarray(rng.randn(batch, SEQ, hkv, d), jnp.float32)
    real = (segment_ids != 0)[:, :, None, None]
    weight = jnp.asarray(rng.randn(batch, SEQ, hq, d), jnp.float32) * real  # (0 at padding: neither output is read there)
    scale = d**-0.5

    def tables(q, k, v):
        return _tpu_splash_attention(q, k, v, segment_ids, scale, interpret=True, window=window)

    def reference(q, k, v, window=window):
        mask = make_attention_mask(batch, SEQ, SEQ, causal=True, segment_ids_q=segment_ids, window=window)
        return sdpa_attention(q, _repeat_kv(k, hq), _repeat_kv(v, hq), mask, None, scale)

    def value_and_gradients(fn):
        return jax.jit(jax.value_and_grad(lambda *x: (fn(*x) * weight).sum(), argnums=(0, 1, 2)))(q, k, v)

    out = jax.jit(tables)(q, k, v)
    np.testing.assert_allclose(np.asarray(out * real), np.asarray(jax.jit(reference)(q, k, v) * real), atol=1e-4, rtol=1e-4)
    longest = max(int(np.bincount(row[row != 0]).max()) for row in np.asarray(segment_ids))
    if longest > window:  # (a row whose every document fits the window is the full layer's)
        assert float(jnp.abs((out - reference(q, k, v, None)) * real).max()) > 1e-2  # the window took keys away
    (_, grads), (_, grads_reference) = map(value_and_gradients, (tables, reference))
    for ours, plain in zip(grads, grads_reference):
        np.testing.assert_allclose(np.asarray(ours), np.asarray(plain), atol=2e-4, rtol=2e-4)


# ---------------------------------------------------------------- tables as wide as the window reaches

BANDED_ROWS = {
    "packed_documents": [_ids([128, 384, 128], SEQ), _ids([40, 300, 200, 100], SEQ)],
    "tail_padding": [_ids([150, 170, 130], SEQ), _ids([500], SEQ)],
    "one_document": [_ids([SEQ], SEQ)],
    "ids_shuffled": [np.random.RandomState(3).permutation(9)[_ids([90, 200, 30, 170, 150], SEQ)].astype(np.int32) + 1],
}
# (window, the key slots its reach gives at five blocks of 128 a row): the diagonal alone, one
# block back, all but a row's first block
BANDED_WINDOWS = {1: 1, 100: 2, 300: 4}


@pytest.mark.parametrize("window", BANDED_WINDOWS)
@pytest.mark.parametrize("rows", BANDED_ROWS)
def test_banded_tables_give_the_row_wide_tables_result_bit_for_bit_and_sdpa_s(rows, window, monkeypatch):
    """Output, dq, dk and dv under tables ``reach + 1`` slots wide: bit for bit those of the
    row-wide tables the commit before this one (aac3fe9) built for every layer (the same blocks
    run in the same order), and `sdpa`'s under the same window wherever a token is no padding."""
    hq, hkv, d = 2, 1, 128
    segment_ids = jnp.asarray(np.stack(BANDED_ROWS[rows]))
    batch, n, slots = segment_ids.shape[0], SEQ // BLOCK, BANDED_WINDOWS[window]
    assert slots == window_block_reach(window, BLOCK) + 1 < n
    rng = np.random.RandomState(len(rows) + window)
    q = jnp.asarray(rng.randn(batch, SEQ, hq, d), jnp.float32)
    k = jnp.asarray(rng.randn(batch, SEQ, hkv, d), jnp.float32)
    v = jnp.asarray(rng.randn(batch, SEQ, hkv, d), jnp.float32)
    real = (segment_ids != 0)[:, :, None, None]
    weight = jnp.asarray(rng.randn(batch, SEQ, hq, d), jnp.float32) * real

    def kernel(q, k, v):
        return _tpu_splash_attention(q, k, v, segment_ids, d**-0.5, interpret=True, window=window)

    def reference(q, k, v):
        mask = make_attention_mask(batch, SEQ, SEQ, causal=True, segment_ids_q=segment_ids, window=window)
        return sdpa_attention(q, _repeat_kv(k, hq), _repeat_kv(v, hq), mask, None, d**-0.5)

    def value_and_gradients(fn):
        def loss(q, k, v):
            out = fn(q, k, v)
            return (out * weight).sum(), out * real
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)

    banded = value_and_gradients(kernel)
    asked = []  # (`append` returns None: the call goes on to the row-wide tables)
    monkeypatch.setattr(attention_ops, "_banded_block_tables", lambda needed, width: asked.append(width) or _document_block_tables(needed))
    jax.tree.map(np.testing.assert_array_equal, banded, value_and_gradients(kernel))
    assert asked == [slots]
    jax.tree.map(lambda ours, plain: np.testing.assert_allclose(np.asarray(ours), np.asarray(plain), atol=2e-4, rtol=2e-4), banded, value_and_gradients(reference))


# (window, the key slots a launch walks at five blocks of 128 a row): under a row's where the
# window reaches fewer; a row's for a window that reaches it all, one longer than the row, and none
GRID_WINDOWS = {1: 1, 100: 2, 200: 3, 300: 4, 400: 5, 2000: 5, None: 5}


@pytest.mark.parametrize("window", GRID_WINDOWS)
def test_a_layer_s_three_launches_walk_the_key_slots_its_window_reaches(window):
    """The grids of forward, dq and dkv read off the gradient's jaxpr: heads x query blocks of
    all rows x key slots (dkv: key blocks x heads x query slots), the slots the window's reach
    plus the diagonal, a row's in a full layer and never more."""
    batch, hq, d, n, slots = 3, 4, 128, SEQ // BLOCK, GRID_WINDOWS[window]
    q = jax.ShapeDtypeStruct((batch, SEQ, hq, d), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((batch, SEQ, 1, d), jnp.bfloat16)
    ids = jax.ShapeDtypeStruct((batch, SEQ), jnp.int32)
    grad = jax.grad(lambda q, k, v, s: _tpu_splash_attention(q, k, v, s, d**-0.5, window=window).astype(jnp.float32).sum(), argnums=(0, 1, 2))
    assert _launch_grids(jax.make_jaxpr(grad)(q, kv, kv, ids)) == {"fwd": (hq, batch * n, slots), "dq": (hq, batch * n, slots), "dkv": (batch * n, hq, slots)}


def test_splash_without_segment_ids_runs_jax_s_own_local_mask():
    """No ids, a window: the static program on jax's `LocalMask`, against the dense mask."""
    rng = np.random.RandomState(5)
    q, k, v = (jnp.asarray(rng.randn(2, 512, heads, 128), jnp.float32) for heads in (2, 1, 1))
    out = _tpu_splash_attention(q, k, v, None, 128**-0.5, interpret=True, window=200)
    mask = make_attention_mask(2, 512, 512, causal=True, window=200)
    np.testing.assert_allclose(out, sdpa_attention(q, _repeat_kv(k, 2), _repeat_kv(v, 2), mask, None, 128**-0.5), atol=1e-4, rtol=1e-4)


def test_the_block_plan_says_the_window_its_reach_and_the_slots_walked(tmp_path, monkeypatch):
    """Which tables a call builds and what its `splash_block_plan` says: only the ids' tables of
    a window that reaches fewer key blocks than a row has are banded, and only that record says
    `key_slots`; every other call builds what it built and says what it said."""
    built = []
    for name in ("_document_block_tables", "_banded_block_tables"):
        monkeypatch.setattr(attention_ops, name, lambda *args, name=name, real=getattr(attention_ops, name): built.append(name) or real(*args))
    sink = tmp_path / "t.jsonl"
    telemetry = Telemetry(sink_path=str(sink), rank=0)
    install_telemetry(telemetry)
    try:
        q = jnp.zeros((2, 4096, 4, 128), jnp.float32)
        ids = jnp.ones((2, 4096), jnp.int32)
        grids = []
        # a second window layer has nothing new to say; a full layer has, and a window that reaches the whole row, and a call without ids
        for window, given in ((2048, ids), (2048, ids), (None, ids), (8192, ids), (2048, None)):
            jaxpr = jax.make_jaxpr(lambda q: _tpu_splash_attention(q, q[:, :, :1], q[:, :, :1], given, 1.0, interpret=True, window=window))(q)
            grids.append(_launch_grids(jaxpr))
    finally:
        uninstall_telemetry()
        telemetry.close()
    assert built == ["_banded_block_tables"] * 2 + ["_document_block_tables"] * 2
    plans = [json.loads(line) for line in sink.read_text().splitlines()]
    plans = [p for p in plans if p["kind"] == "event" and p["event"] == "splash_block_plan"]
    # the cell's blocks at a quarter of its row: 8 key slots a row, 5 under the window
    assert [(p.get("window"), p.get("window_key_blocks"), p.get("key_slots"), p["grid"], p["block_kv"], p["tables"]) for p in plans] == [
        (2048, 5, 5, [4, 16, 5], 512, "segment_ids"), (None, None, None, [4, 16, 8], 512, "segment_ids"),
        (8192, 17, None, [4, 16, 8], 512, "segment_ids"), (2048, 5, None, [4, 16, 8], 512, "static"),
    ]
    # what the commit before this one (aac3fe9) wrote, key for key, wherever the tables are not banded
    full = {"kind", "event", "ts", "rank", "block_q", "block_kv", "rows", "grid", "launches_per_call", "tables", "why_static"}
    assert [set(p) - full for p in plans] == [{"window", "window_key_blocks", "key_slots"}, set(), {"window", "window_key_blocks"}, {"window", "window_key_blocks"}]
    assert all(set(p) >= full for p in plans)
    # the grid a record of the ids' tables says is the forward launch's
    assert grids[:4] == [{"fwd": (4, 16, 5)}] * 2 + [{"fwd": (4, 16, 8)}] * 2


# ---------------------------------------------------------------- the paths that know no window

def test_paths_without_a_window_refuse_one(monkeypatch):
    q = jnp.zeros((1, 256, 2, 128), jnp.float32)
    for implementation in (AttentionImplementation.ring, AttentionImplementation.ulysses):
        with pytest.raises(NotImplementedError, match=f"{implementation.value} attention under a window of 64"):
            attention(q, q, q, implementation=implementation, window=64)
    # the legacy flash kernel (a TPU with the splash family off): refused, not run without the window
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(attention_ops, "_use_splash_kernel", lambda: False)
    with pytest.raises(NotImplementedError, match="legacy flash kernel under a window of 64"):
        attention(q, q, q, implementation=AttentionImplementation.flash_attention_2, window=64)
    with pytest.raises(ValueError, match="a window of 64 keys on non-causal attention"):
        attention(q, q, q, causal=False, window=64)
    with pytest.raises(ValueError, match="a window of 0 keys"):
        attention(q, q, q, window=0)
    # eager takes it
    out = eager_attention(q, q, q, make_attention_mask(1, 256, 256, window=64), None, 1.0)
    assert out.shape == q.shape
