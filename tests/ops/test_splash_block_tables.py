"""The splash kernel's block tables are built from the rows' segment ids.

jax's kernel runs a grid step where its ``block_mask`` table says so and fetches the block its
``data_next`` table names. `ops.attention` builds both each step from the packed rows' segment
ids, so a (query block, key block) pair no document spans is neither fetched nor computed — in
the forward pass, in dkv and in dq. Here on the CPU the kernels run interpreted: these tests
see values (against `sdpa` and against the static causal tables the parent commit ran) and
programs, never a time.
"""

import hashlib
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas.ops.tpu.splash_attention import (
    splash_attention_kernel as sk,
    splash_attention_mask as sm,
)

from dolomite_engine_tpu.enums import AttentionImplementation
from dolomite_engine_tpu.models import config_from_dict, gpt_dolomite
from dolomite_engine_tpu.models.gpt_dolomite import GPTDolomiteForCausalLM
from dolomite_engine_tpu.ops.attention import (
    SPLASH_COUNTERS,
    _banded_block_tables,
    _document_block_tables,
    _pick_block,
    _repeat_kv,
    _tpu_splash_attention,
    document_block_pairs,
    make_attention_mask,
    sdpa_attention,
    splash_block_counters,
    splash_expected,
    window_block_reach,
)
from dolomite_engine_tpu.utils.telemetry import Telemetry, install_telemetry, uninstall_telemetry

SEQ, BLOCK = 640, 128  # `_pick_block(640)` is 128: five blocks a row

# what the benchmark's cells run — the head widths and the group sizes; of the heads, a
# quarter (the tables do not know the heads, and interpreted kernels are slow):
# (query heads, kv heads, scores' head, values' head)
HEAD_SHAPES = {
    "mha_x80": (8, 8, 80, 80),  # granite-3b: 32 x 80
    "gqa_4to1_x128": (8, 2, 128, 128),  # granite-8b: 32 over 8 x 128
    "gqa_16to1_x128": (32, 2, 128, 128),  # the nemotron_h tower: 32 over 2 x 128
    "mla_192_128": (8, 8, 192, 128),  # joyai: 32 heads, scores over 192, values of 128
}


def _ids(lengths, seq=SEQ):
    """Segment ids 1, 2, ... of documents of these lengths, the row's rest padding (0)."""
    ids = np.zeros(seq, np.int32)
    start = 0
    for number, length in enumerate(lengths, start=1):
        ids[start : start + length] = number
        start += length
    assert start <= seq
    return ids


ROWS = {
    # documents that cross block boundaries
    "crossing_blocks": [_ids([200, 250, 190])],
    # one document longer than the row: every block under the diagonal is needed
    "one_document": [_ids([SEQ])],
    # a dozen documents inside the first block, then two long ones
    "dozen_in_a_block": [_ids([10] * 11 + [18] + [300, 212])],
    # padding (id 0) at the row's tail
    "tail_padding": [_ids([150, 170, 130])],
    # two rows with different documents, under one call
    "two_rows": [_ids([128, 384, 128]), _ids([500, 40, 100])],
}


def _static_tables_call(q, k, v, segment_ids, scale):
    """The call as the parent commit made it: jax's static causal tables, the documents in
    the kernel's comparison of segment ids only, `jax.vmap` over the rows."""
    qt, kt, vt = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
    block = _pick_block(q.shape[1])
    sizes = sk.BlockSizes(
        block_q=block, block_kv=block, block_kv_compute=block, block_q_dkv=block, block_kv_dkv=block,
        block_kv_dkv_compute=block, block_q_dq=block, block_kv_dq=block,
    )
    mask = sm.MultiHeadMask([sm.CausalMask((q.shape[1], k.shape[1])) for _ in range(q.shape[2])])
    kernel = sk.make_splash_mha_single_device(mask, block_sizes=sizes, interpret=True)
    out = jax.vmap(lambda a, b, c, s: kernel(a, b, c, segment_ids=sk.SegmentIds(q=s, kv=s)))(
        qt * scale, kt, vt, segment_ids
    )
    return jnp.swapaxes(out, 1, 2)


@pytest.mark.parametrize("rows", ROWS)
@pytest.mark.parametrize("heads", HEAD_SHAPES)
def test_document_tables_give_the_static_tables_result_and_sdpa_s(heads, rows):
    """Output and the gradients of q, k, v of the segmented call: bit for bit those of the
    static causal tables (a skipped block contributed exact zeros), and `sdpa`'s with the
    same ids wherever a token is no padding."""
    hq, hkv, d, dv = HEAD_SHAPES[heads]
    segment_ids = jnp.asarray(np.stack(ROWS[rows]))
    batch = segment_ids.shape[0]
    rng = np.random.RandomState(len(heads) + len(rows))
    q = jnp.asarray(rng.randn(batch, SEQ, hq, d), jnp.float32)
    k = jnp.asarray(rng.randn(batch, SEQ, hkv, d), jnp.float32)
    v = jnp.asarray(rng.randn(batch, SEQ, hkv, dv), jnp.float32)
    real = (segment_ids != 0)[:, :, None, None]
    # the cotangent is 0 at padding: sdpa lets a padding query attend nothing, the kernel lets
    # it attend the padding before it, and neither output is read
    weight = jnp.asarray(rng.randn(batch, SEQ, hq, dv), jnp.float32) * real
    scale = d**-0.5

    def tables(q, k, v):
        return _tpu_splash_attention(q, k, v, segment_ids, scale, interpret=True)

    def static(q, k, v):
        return _static_tables_call(q, k, v, segment_ids, scale)

    def reference(q, k, v):
        mask = make_attention_mask(batch, SEQ, SEQ, causal=True, segment_ids_q=segment_ids)
        return sdpa_attention(q, _repeat_kv(k, hq), _repeat_kv(v, hq), mask, None, scale)

    def value_and_gradients(fn):
        return jax.jit(jax.value_and_grad(lambda *x: (fn(*x) * weight).sum(), argnums=(0, 1, 2)))(q, k, v)

    out = jax.jit(tables)(q, k, v)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(jax.jit(static)(q, k, v)))
    np.testing.assert_allclose(
        np.asarray(out * real), np.asarray(jax.jit(reference)(q, k, v) * real), atol=1e-4, rtol=1e-4
    )
    (loss, grads), (loss_static, grads_static), (_, grads_reference) = map(value_and_gradients, (tables, static, reference))
    assert float(loss) == float(loss_static)
    for ours, theirs, plain in zip(grads, grads_static, grads_reference):
        np.testing.assert_array_equal(np.asarray(ours), np.asarray(theirs))
        np.testing.assert_allclose(np.asarray(ours), np.asarray(plain), atol=2e-4, rtol=2e-4)


# ---------------------------------------------------------------- the tables, a pure function


def _needed_by_brute_force(ids: np.ndarray, block: int) -> np.ndarray:
    """[n, n]: whether some query of block i and some key of block j, the key at or before
    the query, carry the same id."""
    n = len(ids) // block
    same = (ids[:, None] == ids[None, :]) & (np.arange(len(ids))[:, None] >= np.arange(len(ids))[None, :])
    return same.reshape(n, block, n, block).any(axis=(1, 3))


def _random_documents(seed: int, seq: int, median: float = 60.0) -> list[int]:
    rng = np.random.RandomState(seed)
    lengths = []
    while sum(lengths) < seq:
        lengths.append(int(np.clip(rng.lognormal(np.log(median), 1.0), 1, seq)))
    lengths[-1] -= sum(lengths) - seq
    return [n for n in lengths if n > 0]


SEQ_T, BLOCK_T = 1024, 64  # sixteen blocks a row

ID_ROWS = {
    **{f"packed_seed{seed}": (_ids(_random_documents(seed, SEQ_T), SEQ_T), True) for seed in range(6)},
    "one_document": (_ids([SEQ_T], SEQ_T), True),
    "every_token_its_own": (np.arange(1, SEQ_T + 1, dtype=np.int32), True),
    "tail_padding": (_ids(_random_documents(7, SEQ_T - 200), SEQ_T), True),
    "all_padding": (np.zeros(SEQ_T, np.int32), True),
    # a key-side padding mask as ids: left padding, then the prompt
    "left_padding_mask": (np.r_[np.zeros(300, np.int32), np.ones(SEQ_T - 300, np.int32)], True),
    # ids in no order: the ranges are conservative, never wrong
    "ids_shuffled": (np.random.RandomState(3).permutation(40)[_ids(_random_documents(8, SEQ_T), SEQ_T) % 40].astype(np.int32), False),
    "an_id_comes_back": (_ids([100, 300, 200], SEQ_T) % 3, False),
    "padding_in_the_middle": (np.r_[_ids([200, 100], 400), _ids([300, 324], 624)], False),
}


@pytest.mark.parametrize("case", ID_ROWS)
def test_block_pairs_never_skip_a_needed_pair_and_are_exact_on_ordered_ids(case):
    ids, ordered = ID_ROWS[case]
    needed = np.asarray(document_block_pairs(jnp.asarray(ids)[None], BLOCK_T))[0]
    brute = _needed_by_brute_force(ids, BLOCK_T)
    assert not (brute & ~needed).any()  # a needed pair is never skipped
    assert not np.triu(needed, 1).any() and needed.diagonal().all()
    if ordered:
        np.testing.assert_array_equal(needed, brute)


def _walk(needed_blocks: np.ndarray, grid) -> dict:
    """What `data_next` should say, by walking a launch's grid in its order: at a step that
    runs its own block, at a skipped one the block of the next step that runs."""
    steps = list(grid)
    runs = [step for step in steps if needed_blocks[step]]
    expected, upcoming = {}, 0
    for step in steps:
        while upcoming < len(runs) and runs[upcoming] < step:
            upcoming += 1
        expected[step] = runs[upcoming] if upcoming < len(runs) else None
    return expected


@pytest.mark.parametrize("case", ["packed_seed0", "packed_seed1", "tail_padding", "one_document", "ids_shuffled"])
def test_tables_name_the_running_step_s_block_or_the_next_one_that_runs(case):
    """Two rows under one call, laid end to end: forward / dq tables ``[1, B n, n]``, dkv's
    ``[1, n, B n]``; a step that runs fetches its own block, a skipped one the next running
    step's, so skipping moves no block the kernel does not need."""
    rows = np.stack([ID_ROWS[case][0], ID_ROWS["packed_seed2"][0]])
    needed = np.asarray(document_block_pairs(jnp.asarray(rows), BLOCK_T))
    (block_mask, data_next), (block_mask_dkv, data_next_dkv) = jax.tree.map(np.asarray, _document_block_tables(jnp.asarray(needed)))
    batch, n, _ = needed.shape
    assert block_mask.shape == data_next.shape == (1, batch * n, n)
    assert block_mask_dkv.shape == data_next_dkv.shape == (1, n, batch * n)

    # forward and dq: (query block, key slot), query blocks of all rows in turn
    running = needed.reshape(batch * n, n)
    np.testing.assert_array_equal(block_mask[0] != 0, running)
    expected = _walk(running, ((i, j) for i in range(batch * n) for j in range(n)))
    for (i, j), step in expected.items():
        assert data_next[0, i, j] == (step[0] // n) * n + step[1], (i, j)

    # dkv: (key block, query slot); past a key block's last query block the next head starts
    # over at the same key block, whose first running step is its diagonal
    running = needed.transpose(0, 2, 1).reshape(batch * n, n)  # [key block, query slot]
    np.testing.assert_array_equal(block_mask_dkv[0].T != 0, running)
    for key in range(batch * n):
        expected = _walk(running[key], range(n))
        for slot, step in expected.items():
            first_of_row = (key // n) * n
            assert data_next_dkv[0, slot, key] == first_of_row + (key % n if step is None else step), (key, slot)


@pytest.mark.parametrize("window", [1, 64, 200, 850])  # at blocks of 64: 1, 2, 5 and 16 - 1 key slots
@pytest.mark.parametrize("case", ["packed_seed0", "packed_seed1", "tail_padding", "one_document", "ids_shuffled"])
def test_banded_tables_run_the_row_wide_tables_steps_in_a_band_as_wide_as_the_window_reaches(case, window):
    """The same two rows under a window that reaches fewer key blocks than a row has: forward /
    dq tables ``[1, B n, W]`` whose slot s of query block i is key block ``i - (W - 1) + s``,
    dkv's ``[1, W, B n]`` whose slot s of key block j is query block ``j + s``. Laid back over a
    row's slots they are the row-wide tables' steps (every needed pair has exactly one running
    step); a step that runs names its own block, a skipped one the next that runs in its own
    band (else the diagonal), and every entry of both `data_next` tables lies in its own row —
    the index maps fetch what it names, and the chip does not check."""
    rows = np.stack([ID_ROWS[case][0], ID_ROWS["packed_seed2"][0]])
    needed = document_block_pairs(jnp.asarray(rows), BLOCK_T, window)
    batch, n, _ = needed.shape
    width = window_block_reach(window, BLOCK_T) + 1
    assert width < n
    (wide_mask, wide_next), (wide_mask_dkv, wide_next_dkv) = jax.tree.map(np.asarray, _document_block_tables(needed))
    (block_mask, data_next), (block_mask_dkv, data_next_dkv) = jax.tree.map(np.asarray, _banded_block_tables(needed, width))
    assert block_mask.shape == data_next.shape == (1, batch * n, width)
    assert block_mask_dkv.shape == data_next_dkv.shape == (1, width, batch * n)
    row_of = np.arange(batch * n) // n

    def over_the_row(band, partner):
        """[B n, W] of a band's slots -> [B n, n] of a row's: slot s of block (row, i) is the row's slot partner(i, s)."""
        laid = np.zeros((batch * n, n), band.dtype)
        for block in range(batch * n):
            for slot in range(width):
                if 0 <= partner(block % n, slot) < n:
                    laid[block, partner(block % n, slot)] = band[block, slot]
                else:
                    assert band[block, slot] == 0, (block, slot)  # a slot outside the row never runs
        return laid

    # forward and dq: the band's running steps are the row-wide table's, and name the blocks it names
    key_of = lambda i, s: i - (width - 1) + s  # noqa: E731
    np.testing.assert_array_equal(over_the_row(block_mask[0], key_of), wide_mask[0])
    running = block_mask[0] != 0
    assert running.sum() == int(needed.sum()) and running[:, -1].all()  # the diagonal, the last slot, always runs
    np.testing.assert_array_equal(over_the_row(np.where(running, data_next[0], 0), key_of), np.where(wide_mask[0] != 0, wide_next[0], 0))
    expected = _walk(running, ((i, s) for i in range(batch * n) for s in range(width)))
    for (i, s), step in expected.items():
        assert data_next[0, i, s] == step[0] - (width - 1) + step[1], (i, s)
        assert step[0] == i  # the next step that runs is one of the same query block
    np.testing.assert_array_equal(data_next[0] // n, np.broadcast_to(row_of[:, None], data_next[0].shape))

    # dkv: the band of each key block; past a key block's last running step the next head
    # starts over at its diagonal, slot 0
    query_of = lambda j, s: j + s  # noqa: E731
    np.testing.assert_array_equal(over_the_row(block_mask_dkv[0].T, query_of), wide_mask_dkv[0].T)
    running = block_mask_dkv[0].T != 0  # [key block, query slot]
    assert running.sum() == int(needed.sum()) and running[:, 0].all()
    np.testing.assert_array_equal(over_the_row(np.where(running, data_next_dkv[0].T, 0), query_of), np.where(wide_mask_dkv[0].T != 0, wide_next_dkv[0].T, 0))
    for key in range(batch * n):
        expected = _walk(running[key], range(width))
        for slot, step in expected.items():
            assert data_next_dkv[0, slot, key] == key + (0 if step is None else step), (key, slot)
    np.testing.assert_array_equal(data_next_dkv[0] // n, np.broadcast_to(row_of[None, :], data_next_dkv[0].shape))


@pytest.mark.parametrize("case", ["packed_seed0", "tail_padding", "one_document", "every_token_its_own"])
def test_counters_count_the_tables(case):
    ids = ID_ROWS[case][0]
    rows = jnp.asarray(np.stack([ids, ID_ROWS["packed_seed3"][0]]))
    block = _pick_block(SEQ_T)
    n = SEQ_T // block
    counted = jax.jit(lambda r: splash_block_counters(2, SEQ_T, r))(rows)
    assert set(counted) == set(SPLASH_COUNTERS)
    brute = sum(int(_needed_by_brute_force(np.asarray(row), block).sum()) for row in rows)
    assert int(counted["splash_blocks_visited"]) == brute
    assert int(counted["splash_blocks_causal"]) == 2 * n * (n + 1) // 2
    # no segment ids: the static tables run the whole triangle; a length the kernel does not take: nothing
    assert {k: int(v) for k, v in splash_block_counters(2, SEQ_T).items()} == dict.fromkeys(SPLASH_COUNTERS, 2 * n * (n + 1) // 2)
    assert {k: int(v) for k, v in splash_block_counters(2, 100, rows[:, :100]).items()} == dict.fromkeys(SPLASH_COUNTERS, 0)


# ---------------------------------------------------------------- the programs

# sha256 of the call without segment ids as the parent commit (8008775) traced it: the jaxpr
# of the value and of the gradient at 2 rows x 1024 x (4 over 2 heads) x 128 in bfloat16, and
# its lowering for a TPU with the Mosaic kernels' serialized bodies cut out (they carry the
# source lines of ops/attention.py, which move with every edit)
PARENT_PROGRAMS = {
    "fwd": ("461b71a2f9849bb4", "4c55805fbef9c5d5"),
    "grad": ("a88187169a8bc096", "372099bcc6069f07"),
}


def _digest(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("program", PARENT_PROGRAMS)
def test_a_call_without_segment_ids_is_the_parent_s_program(program):
    """No segment ids, nothing to build tables from: the static causal program, as it was."""
    batch, seq, hq, hkv, d = 2, 1024, 4, 2, 128
    q = jax.ShapeDtypeStruct((batch, seq, hq, d), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((batch, seq, hkv, d), jnp.bfloat16)
    fn = lambda q, k, v: _tpu_splash_attention(q, k, v, None, d**-0.5)  # noqa: E731
    if program == "grad":
        fn = jax.grad(lambda q, k, v: _tpu_splash_attention(q, k, v, None, d**-0.5).astype(jnp.float32).sum(), argnums=(0, 1, 2))
    jaxpr, lowered = PARENT_PROGRAMS[program]
    assert _digest(str(jax.make_jaxpr(fn)(q, kv, kv))) == jaxpr
    text = jax.jit(fn).trace(q, kv, kv).lower(lowering_platforms=("tpu",)).as_text()
    assert _digest(re.sub(r'\\22body\\22: \\22[^\\]*\\22', "body", text)) == lowered


def _pallas_calls(jaxpr, under=(), found=None) -> list:
    """(launch, the primitives it sits under) of every Pallas launch of `jaxpr`."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append((eqn, under))
            continue
        for value in eqn.params.values():
            for item in value if isinstance(value, (tuple, list)) else (value,):
                inner = getattr(item, "jaxpr", item)
                if hasattr(inner, "eqns"):
                    _pallas_calls(inner, under + (eqn.primitive.name,), found)
    return found


def _launch_grids(jaxpr) -> dict:
    """{"fwd" | "dq" | "dkv": grid} of the splash launches of `jaxpr` (their names: ``splash_mha_<which>_...``)."""
    return {eqn.params["name"].split("_")[2]: tuple(eqn.params["grid_mapping"].grid) for eqn, _ in _pallas_calls(jaxpr.jaxpr)}


def test_segmented_gradient_is_three_launches_over_all_rows_and_no_loop_over_rows():
    """Forward, dkv and dq, each one launch for the call's rows laid end to end (grid: heads x
    query blocks of all rows x key slots of one row), their tables traced values — not a loop
    of slices over the rows, which is how Pallas batches a per-row scalar-prefetch operand."""
    batch, seq, hq, hkv, d = 3, 1024, 4, 2, 128
    q = jax.ShapeDtypeStruct((batch, seq, hq, d), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((batch, seq, hkv, d), jnp.bfloat16)
    ids = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    grad = jax.grad(lambda q, k, v, s: _tpu_splash_attention(q, k, v, s, d**-0.5).astype(jnp.float32).sum(), argnums=(0, 1, 2))
    jaxpr = jax.make_jaxpr(grad)(q, kv, kv, ids)
    calls = _pallas_calls(jaxpr.jaxpr)
    n = seq // _pick_block(seq)
    assert _launch_grids(jaxpr) == {"fwd": (hq, batch * n, n), "dq": (hq, batch * n, n), "dkv": (batch * n, hq, n)}
    for eqn, under in calls:
        assert not {"while", "scan"} & set(under), under
        # block_mask and data_next are operands computed from the ids, no constants
        assert all(type(v).__name__ != "Literal" for v in eqn.invars[:2])


def test_splash_block_plan_is_written_once_a_distinct_plan(tmp_path):
    sink = tmp_path / "t.jsonl"
    telemetry = Telemetry(sink_path=str(sink), rank=0)
    install_telemetry(telemetry)
    try:
        q = jnp.zeros((2, SEQ, 4, 128), jnp.float32)
        ids = jnp.asarray(np.stack(ROWS["two_rows"]))
        for _ in range(2):  # a second layer, a second trace: nothing new to say
            jax.make_jaxpr(lambda q: _tpu_splash_attention(q, q, q, ids, 1.0, interpret=True))(q)
        jax.make_jaxpr(lambda q: _tpu_splash_attention(q, q, q, None, 1.0, interpret=True))(q)
    finally:
        uninstall_telemetry()
        telemetry.close()
    plans = [json.loads(line) for line in sink.read_text().splitlines()]
    plans = [p for p in plans if p["kind"] == "event" and p["event"] == "splash_block_plan"]
    assert [(p["tables"], p["why_static"], p["launches_per_call"]) for p in plans] == [
        ("segment_ids", None, 1),
        ("static", "the call has no segment ids", 2),
    ]
    assert all((p["block_q"], p["block_kv"], p["rows"], p["grid"]) == (BLOCK, BLOCK, 2, [4, 2 * SEQ // BLOCK, SEQ // BLOCK]) for p in plans)
    assert not any("key_slots" in p or "window" in p for p in plans)  # no window: a launch walks a row's key slots, and the record says no other


# ---------------------------------------------------------------- the counters a step returns


def _dense_model():
    config = config_from_dict(
        dict(
            model_type="gpt_dolomite", vocab_size=256, n_positions=SEQ, n_embd=64, n_layer=2, n_head=2,
            attention_head_type="mha", position_embedding_type="rope", activation_function="swiglu",
            normalization_function="rmsnorm", add_bias=False, n_inner=64, resid_pdrop=0.0, embd_pdrop=0.0,
            attn_pdrop=0.0, bos_token_id=0, eos_token_id=1, pad_token_id=2,
        )
    )
    return GPTDolomiteForCausalLM(config=config, attention_implementation=AttentionImplementation.flash_attention_2)


@pytest.mark.parametrize("given", ["segment_ids", "attention_mask", "neither"])
def test_dense_family_returns_the_splash_counters_where_the_kernel_is_expected(given, monkeypatch):
    """Here on the CPU the kernel is not expected and the dense family counts nothing, as
    before; where it is (a TPU: `splash_expected`), its forward pass returns one layer's
    worth of visited and causal block pairs for the step's rows."""
    model = _dense_model()
    rows = np.stack(ROWS["two_rows"])
    tokens = jnp.asarray(np.random.RandomState(0).randint(3, 256, size=rows.shape), jnp.int32)
    inputs = {"segment_ids": dict(segment_ids=jnp.asarray(rows)), "attention_mask": dict(attention_mask=jnp.asarray(rows != 3)), "neither": {}}[given]
    params = model.init(jax.random.PRNGKey(0), tokens)
    assert model.step_counter_names == ()
    assert model.apply(params, tokens, labels=tokens, **inputs).counters is None

    monkeypatch.setattr(gpt_dolomite, "splash_expected", lambda implementation: implementation == AttentionImplementation.flash_attention_2)
    assert model.step_counter_names == SPLASH_COUNTERS
    counters = model.apply(params, tokens, labels=tokens, **inputs).counters
    n = SEQ // BLOCK
    # a key-side padding mask reaches the kernel as ids 1 / 0
    as_ids = {"segment_ids": rows, "attention_mask": (rows != 3).astype(np.int32), "neither": np.ones_like(rows)}[given]
    visited = sum(int(_needed_by_brute_force(r, BLOCK).sum()) for r in as_ids)
    assert {k: int(v) for k, v in counters.items()} == {"splash_blocks_visited": visited, "splash_blocks_causal": 2 * n * (n + 1) // 2}
    assert splash_expected(AttentionImplementation.flash_attention_2) is False  # (the CPU)


@pytest.mark.parametrize("counts", [False, True], ids=["loss", "loss_and_counters"])
def test_eval_step_reads_the_loss_of_a_family_that_counts(counts):
    """`pretrain.evaluate` takes `float()` of the eval step: a wrapper whose family counts
    (on a TPU now the dense one too) returns (loss, counters) from `loss`."""
    from dolomite_engine_tpu.train_utils import make_eval_step

    class Wrapper:
        def loss(self, params, batch, rngs=None, train=True, fp8_state=None):
            loss = jnp.mean(params * batch)
            return (loss, splash_block_counters(1, 1024)) if counts else loss

    assert float(jax.jit(make_eval_step(Wrapper()))(jnp.ones(4), jnp.full(4, 2.0))) == 2.0
