"""The TPU's own compiler on the kernels of the main path, without a chip.

The interpret-mode parity suite (`test_pallas_kernels.py`) pins what the kernels compute;
it cannot see what Mosaic refuses — a block whose last dim is not a multiple of 128, a
primitive without a TPU lowering, a kernel GSPMD cannot partition. libtpu is installed
here and compiles for a chip that is described, not attached
(`jax.experimental.topologies`), so every Pallas family the promotion table turns on for
a TPU (`ops/pallas/config._PLATFORM_PROMOTIONS`) is compiled with ``interpret=False`` at
the widths that run: the flagship `chip_smoke.py` drives (n_embd 2560, 32 heads of 80,
seq 4096, 16-token pages, the engine's 512-token prefill chunk) and a GQA
configuration (n_embd 1024, 16 query / 8 kv heads of 64, seq 2048). A family that stops
compiling fails here, at no chip time; one that is demoted leaves this file with its row
in the table. Nothing runs, so nothing here says anything about results or speed.
"""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or libtpu logs under /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from dolomite_engine_tpu.ops.attention import _tpu_splash_attention
from dolomite_engine_tpu.ops.pallas.config import _PLATFORM_PROMOTIONS
from dolomite_engine_tpu.ops.pallas.kv_quant import quantize_pages_pallas
from dolomite_engine_tpu.ops.pallas.rmsnorm import fused_rmsnorm
from dolomite_engine_tpu.ops.pallas.rope_qkv import fused_rope_qkv
from dolomite_engine_tpu.parallel.mesh import MESH_AXES
from dolomite_engine_tpu.parallel.sharding import get_logical_axis_rules, logical_constraint

# name -> (n_embd, query heads, kv heads, head_dim, batch, seq)
WIDTHS = {
    "smoke_2560_32x80_s4096": (2560, 32, 32, 80, 2, 4096),
    "bench_1024_16-8x64_s2048": (1024, 16, 8, 64, 8, 2048),
    # the 8B cell's: at 4096 wide a 256-row block of the norm with a residual is over the
    # scoped VMEM a kernel gets (PR 31: it had compiled only inside the whole step, where XLA
    # kept its outputs in VMEM)
    "granite8b_4096_32-8x128_s4096": (4096, 32, 8, 128, 2, 4096),
}
DECODE_SLOTS, PREFILL_CHUNK, PAGE_SIZE = 8, 512, 16


@pytest.fixture(scope="module")
def v5e():
    """Four described v5e devices — and the persistent compile cache off while this
    module compiles: an executable for a described chip is written to it but cannot be
    read back without a chip."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # libtpu greets a machine without a TPU on the process's stderr, past pytest's capture
    # and into the middle of its progress line: keep fd 2 shut while it starts
    stderr_fd = os.dup(2)
    with open(os.devnull, "w") as devnull:
        os.dup2(devnull.fileno(), 2)
        try:
            devices = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices
        except Exception as error:  # no libtpu here, or one that cannot describe a v5e
            pytest.skip(f"no TPU topology description here: {error!r}")
        finally:
            os.dup2(stderr_fd, 2)
            os.close(stderr_fd)
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield devices
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _sum_sq(*outputs) -> jax.Array:
    return sum((out.astype(jnp.float32) ** 2).sum() for out in outputs)


def _rmsnorm(case: str, width):
    embd, _, _, _, batch, seq = width
    rows = (DECODE_SLOTS, 1, embd) if case == "decode" else (batch, seq, embd)
    x, weight = jax.ShapeDtypeStruct(rows, jnp.bfloat16), jax.ShapeDtypeStruct((embd,), jnp.float32)
    if case == "fwd":
        return (lambda x, w: fused_rmsnorm(x, w, 1e-5, interpret=False)), (x, weight)
    residual = lambda x, r, w: fused_rmsnorm(x, w, 1e-5, residual=r, interpret=False)
    if case == "grad":
        return jax.grad(lambda x, r, w: _sum_sq(*residual(x, r, w)), argnums=(0, 1, 2)), (x, x, weight)
    return residual, (x, x, weight)


def _rope_qkv(case: str, width):
    _, heads, kv_heads, head_dim, batch, seq = width
    batch, seq = {"decode": (DECODE_SLOTS, 1), "chunk": (1, PREFILL_CHUNK)}.get(case, (batch, seq))
    qkv = jax.ShapeDtypeStruct((batch, seq, (heads + 2 * kv_heads) * head_dim), jnp.bfloat16)
    table = jax.ShapeDtypeStruct((batch, seq, head_dim), jnp.bfloat16)
    rope = lambda qkv, cos, sin: fused_rope_qkv(qkv, cos, sin, heads, kv_heads, head_dim, interpret=False)
    if case == "grad":
        return jax.grad(lambda qkv, cos, sin: _sum_sq(rope(qkv, cos, sin))), (qkv, table, table)
    return rope, (qkv, table, table)


def _splash(case: str, width):
    _, heads, kv_heads, head_dim, batch, seq = width
    q = jax.ShapeDtypeStruct((batch, seq, heads, head_dim), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((batch, seq, kv_heads, head_dim), jnp.bfloat16)
    segments = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    attend = lambda q, k, v, seg: _tpu_splash_attention(q, k, v, seg, head_dim**-0.5)
    if case == "grad":
        return (
            jax.grad(lambda q, k, v, seg: _sum_sq(attend(q, k, v, seg)), argnums=(0, 1, 2)),
            (q, kv, kv, segments),
        )
    return attend, (q, kv, kv, segments)


def _paged_kv_quant(case: str, width):
    _, _, kv_heads, head_dim, _, _ = width
    # the pages one decode step re-encodes: a write window of two pages per slot
    pages = jax.ShapeDtypeStruct((2 * DECODE_SLOTS, PAGE_SIZE, kv_heads, head_dim), jnp.float32)
    valid = jax.ShapeDtypeStruct((2 * DECODE_SLOTS, PAGE_SIZE), jnp.bool_)
    return (lambda v, m: quantize_pages_pallas(v, m, 127.0, jnp.int8, interpret=False)), (pages, valid)


# family -> (builder, cases); the families of the generic TPU row, no more and no fewer
FAMILIES = {
    "rmsnorm": (_rmsnorm, ("fwd", "residual", "grad", "decode")),
    "fused_rope_qkv": (_rope_qkv, ("fwd", "grad", "decode", "chunk")),
    "splash_attention": (_splash, ("fwd", "grad")),
    "paged_kv_quant": (_paged_kv_quant, ("int8",)),
}


def test_every_promoted_family_is_compiled_here():
    assert set(FAMILIES) == set(_PLATFORM_PROMOTIONS["tpu"])


@pytest.mark.parametrize("width", WIDTHS)
@pytest.mark.parametrize(
    "family,case", [(family, case) for family, (_, cases) in FAMILIES.items() for case in cases]
)
def test_kernel_compiles_for_v5e(v5e, family, case, width):
    fn, args = FAMILIES[family][0](case, WIDTHS[width])
    one_chip = SingleDeviceSharding(v5e[0])
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip) for a in args]
    assert "tpu_custom_call" in jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("case", ["fwd", "grad"])
def test_mamba2_scan_kernel_compiles_for_v5e_at_the_cell_s_shapes(v5e, case):
    """The Mamba-2 scan's kernels (`ops/pallas/mamba2.py`; not a family of the promotion
    table: `ops/mamba2.mamba2_scan` chooses them from the trace) at the shapes of
    `train-nemotron-tower-packed8k`: 2 rows of 8192, 64 heads of 64 in 8 groups, state 128,
    chunk 128, bfloat16. ``grad`` holds the forward launch that keeps the entering states
    and the backward launch."""
    from dolomite_engine_tpu.ops.pallas.mamba2 import mamba2_chunked_kernel

    batch, seq, heads, width, groups, state = 2, 8192, 64, 64, 8, 128
    one_chip = SingleDeviceSharding(v5e[0])
    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731
    args = (
        spec((batch, seq, heads, width), jnp.bfloat16),
        spec((batch, seq, heads), jnp.float32),
        spec((heads,), jnp.float32),
        spec((batch, seq, groups, state), jnp.bfloat16),
        spec((batch, seq, groups, state), jnp.bfloat16),
        spec((heads,), jnp.float32),
        spec((batch, seq), jnp.int32),
    )
    scan = lambda *a: mamba2_chunked_kernel(*a, 128, interpret=False)  # noqa: E731
    launches = 1
    if case == "grad":
        scan, launches = jax.grad(lambda *a: _sum_sq(mamba2_chunked_kernel(*a, 128, interpret=False)), argnums=tuple(range(6))), 2
    text = jax.jit(scan).lower(*args).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == launches
    # no [L, L] tensor over chunks and heads outside the kernels
    assert not re.search(r"\[2,64,(8,8|64),128,128\]", text)


@pytest.mark.parametrize("capacity, width", [pytest.param(65536, 3072, id="lfm2"), pytest.param(32768, 1536, id="joyai")])
def test_routed_row_blocks_kernel_compiles_for_v5e_at_the_cells_shapes(v5e, monkeypatch, capacity, width):
    """The activation between the held experts' grouped products as one launch a pass
    (`ops/pallas/moe.routed_row_blocks`, chosen by `ops/moe._share_activation`), forward and
    backward, at the widths and the blocks the two gated cells run: Mosaic takes the float32
    arithmetic, the split of a gated row and the blocks within the VMEM the launch asks for."""
    from dolomite_engine_tpu.ops import moe
    from dolomite_engine_tpu.ops.activations import get_activation_function
    from dolomite_engine_tpu.utils import packages

    monkeypatch.setattr(moe, "_one_tpu", lambda rows: True)
    monkeypatch.setattr(packages, "pallas_interpret_mode", lambda: False)
    one_chip = SingleDeviceSharding(v5e[0])
    h = jax.ShapeDtypeStruct((capacity, width), jnp.bfloat16, sharding=one_chip)
    plan = moe._share_activation(h, get_activation_function("swiglu"), capacity, width)
    assert plan.form == "pallas"
    both = jax.value_and_grad(lambda h, count: _sum_sq(moe._activate_rows(plan, h, count)))
    text = jax.jit(both).lower(h, jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2


def _sharded_block_gradient_text(v5e, width, wrap=lambda block: block) -> str:
    """The compiled value and gradient of norm, rope+QKV and splash under fsdp 2 x tp 2 with
    sequence parallelism; `wrap` puts the block under a `jax.checkpoint`."""
    embd, heads, kv_heads, head_dim, batch, seq = WIDTHS[width]
    mesh = Mesh(np.asarray(v5e).reshape(1, 2, 1, 2, 1), MESH_AXES)
    fused = (heads + 2 * kv_heads) * head_dim

    def block(x, residual, weight, w_qkv, cos, sin, segments):
        h, stream = fused_rmsnorm(x, weight, 1e-5, residual=residual, interpret=False)
        qkv = logical_constraint(h @ w_qkv, ("act_batch", "act_seq_inner", "act_heads"))
        qkv = fused_rope_qkv(qkv, cos, sin, heads, kv_heads, head_dim, interpret=False)
        q, k, v = jnp.split(qkv, [heads * head_dim, (heads + kv_heads) * head_dim], axis=-1)
        out = _tpu_splash_attention(
            q.reshape(batch, seq, heads, head_dim),
            k.reshape(batch, seq, kv_heads, head_dim),
            v.reshape(batch, seq, kv_heads, head_dim),
            segments,
            head_dim**-0.5,
        )
        return _sum_sq(out, stream)

    def spec(shape, dtype, *axes):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(mesh, P(*axes)))

    activations = spec((batch, seq, embd), jnp.bfloat16, "fsdp", "tp")
    table = spec((1, seq, head_dim), jnp.bfloat16)
    args = (
        activations,
        activations,
        spec((embd,), jnp.float32),
        spec((embd, fused), jnp.bfloat16, "fsdp", "tp"),
        table,
        table,
        spec((batch, seq), jnp.int32, "fsdp"),
    )
    with mesh, nn.logical_axis_rules(get_logical_axis_rules(stage=3, sequence_parallel=True)):
        return jax.jit(jax.value_and_grad(wrap(block), argnums=(0, 3))).lower(*args).compile().as_text()


@pytest.mark.parametrize("width", WIDTHS)
def test_sharded_block_compiles_for_v5e_2x2(v5e, width):
    """Norm, rope+QKV and splash, forward and backward, under fsdp 2 x tp 2 with
    sequence parallelism — the layout `chip_smoke.py --chips 4` trains on. GSPMD refuses
    to partition a Mosaic kernel, so each has to arrive inside a `shard_map`
    (`parallel.sharding.shard_kernel`), also where the backward is traced."""
    text = _sharded_block_gradient_text(v5e, width)
    assert text.count('custom_call_target="tpu_custom_call"') >= 6  # 1 + 2 + 3 kernels, fwd + bwd


@pytest.mark.parametrize(
    "policy, forward_kernels", [("save_dots", 1), ("dots_saveable", 2), ("full", 1), ("nothing_saveable", 2)]
)
def test_remat_policy_reaches_the_kernel_inside_its_shard_map_for_v5e_2x2(v5e, policy, forward_kernels):
    """The same block under `jax.checkpoint`, as a remat'ed layer is: `save_dots` and `full` keep
    the splash kernel's output and log-sum-exp by their name, through the `shard_map` the mesh
    puts the kernel in, and the compiled gradient holds the forward kernel once; a policy
    without the name (the raw names) runs it again in the backward pass."""
    from dolomite_engine_tpu.models.gpt_dolomite import resolve_remat_policy

    remat = lambda block: jax.checkpoint(block, policy=resolve_remat_policy(policy))  # noqa: E731
    text = _sharded_block_gradient_text(v5e, "smoke_2560_32x80_s4096", remat)
    assert len(re.findall(r"%splash_mha_fwd\S* = ", text)) == forward_kernels
    assert len(re.findall(r"%splash_mha_dkv\S* = ", text)) == 1


def test_sharded_fused_loss_compiles_for_v5e_2x2(v5e):
    """The chunked loss, forward and backward, at the flagship's head (2560 x 49152, one
    packed row of 4096 a device) under fsdp 2 x tp 2 with the table over tp — the layout
    `chip_smoke.py --chips 4` trains on. The differentiated forward keeps ONE block of a
    device's logits, 4096 tokens against its tp shard's 24576 rows in bf16 (192 MiB: half
    the budget, so no loop is left), and the table is gathered (its embed axis, over fsdp)
    once."""
    from dolomite_engine_tpu.ops.loss import fused_linear_cross_entropy
    from dolomite_engine_tpu.utils.program_signature import hlo_collectives

    embd, vocab, batch, seq = 2560, 49152, 2, 4096
    mesh = Mesh(np.asarray(v5e).reshape(1, 2, 1, 2, 1), MESH_AXES)

    def loss(hidden, table, labels):
        return fused_linear_cross_entropy(
            hidden, table, labels, chunk_size=256, compute_dtype=jnp.bfloat16, z_loss_coef=1e-4
        )

    def spec(shape, dtype, *axes):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(mesh, P(*axes)))

    args = (
        spec((batch, seq, embd), jnp.bfloat16, "fsdp", "tp"),
        spec((vocab, embd), jnp.float32, "tp", "fsdp"),
        spec((batch, seq), jnp.int32, "fsdp"),
    )
    rules = get_logical_axis_rules(
        stage=3, sequence_parallel=True, tensor_parallel_word_embeddings=True
    )
    with mesh, nn.logical_axis_rules(rules):
        compiled = jax.jit(
            jax.value_and_grad(loss, argnums=(0, 1)),
            out_shardings=(None, (args[0].sharding, args[1].sharding)),
        ).lower(*args).compile()
    gathers = [
        (dims, in_loop)
        for kind, dims, in_loop in hlo_collectives(compiled.as_text())
        if kind == "all-gather"
    ]
    table_rows = {vocab, vocab // 2, 1536}  # the table, a tp shard of it, a tile's rows
    of_table = [g for g in gathers if g[0][-1] == embd and table_rows & set(g[0])]
    assert of_table == [((vocab // 2, embd), False)], gathers
    assert " while(" not in compiled.as_text()
    # a device's temporaries: the kept block (192 MiB), the gathered bf16 shard and its
    # gradient (120 MiB each): 453 MiB here (the tiled rule PR 39 replaced stayed under 300)
    assert compiled.memory_analysis().temp_size_in_bytes < 500 * 2**20


def _compiled_cell_step(v5e, cell_name: str):
    """The whole train step of a benchmark cell driven by `train_packed_tower`, built from the
    cell's files as that driver builds its arguments, compiled for one described v5e."""
    import types

    from benchmark.drivers.train_packed import build_training_args
    from benchmark.drivers.train_packed_tower import model_config
    from benchmark.spec import Spec
    from dolomite_engine_tpu import pretrain
    from dolomite_engine_tpu.distributed import TrainState
    from dolomite_engine_tpu.enums import Mode
    from dolomite_engine_tpu.ops.pallas import config as pallas_config
    from dolomite_engine_tpu.ops.pallas import install_kernel_config
    from dolomite_engine_tpu.train_utils import make_train_step
    from dolomite_engine_tpu.utils import packages

    one_chip = SingleDeviceSharding(v5e[0])
    cell = Spec.load().cell(cell_name)
    ctx = types.SimpleNamespace(cell=cell, tiny=False, seed=1, out_dir="/nonexistent")
    cfg = model_config(ctx)
    args = build_training_args(ctx, cfg, "/nonexistent/corpus", 10)
    rows, seq = args.training_parameters.micro_batch_size, cfg["n_positions"]

    saved = (jax.default_backend, pallas_config._PLATFORM_KEY, packages.pallas_interpret_mode)
    jax.default_backend = lambda: "tpu"  # ops/attention asks it before choosing splash
    pallas_config._PLATFORM_KEY = "tpu:v5e"
    packages.pallas_interpret_mode = lambda: False
    try:
        args.kernel_args.install()
        model = pretrain.get_model(args, Mode.training)
        optimizer, _ = pretrain.build_optimizer_from_args(args, model)

        def init():
            params = nn.unbox(model.model.init(jax.random.PRNGKey(0), **model.get_dummy_inputs())["params"])
            return TrainState(step=jnp.zeros((), jnp.int32), params=params, opt_state=optimizer.init(params), fp8=None)

        place = lambda tree: jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)  # noqa: E731
        step = jax.jit(
            make_train_step(
                lambda params, micro, rng: model.loss(params, micro["text"], rngs={"dropout": rng}, train=True),
                optimizer, gradient_clipping=1.0, skip_nonfinite=True, has_aux=bool(model.step_counter_names),
            ),
            donate_argnums=(0,),
        )
        compiled = step.lower(
            place(jax.eval_shape(init)),
            {"text": jax.ShapeDtypeStruct((1, rows, seq + 1), jnp.int32, sharding=one_chip)},
            jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip),
        ).compile()
    finally:
        jax.default_backend, pallas_config._PLATFORM_KEY, packages.pallas_interpret_mode = saved
        install_kernel_config(None)
    return compiled


# the compiler's estimate of a step's temporaries at the commit before PR 34 (2d44579, this
# installation: the expert layers gathered and scattered `capacity` rows at once), GiB
TEMPORARIES_BEFORE_THE_WALKS = {
    "train-nemotron-tower-packed8k": 4.576,
    "train-joyai-flash-mtp-packed8k": 6.964,
    "train-lfm2-moe-packed8k": 11.071,
}


# what a cell's blocks keep of the attention kernel under `full` since PR 43, GiB: a block that attends
# keeps the output [heads, rows x S, v_head] in bf16 and the float32 log-sum-exp [heads, rows x S]
KERNEL_RESIDUALS_KEPT = {
    "train-nemotron-tower-packed8k": 1 * (128 + 2) / 1024,  # 32 heads x 128, 16,384 tokens, 1 attention layer
    "train-joyai-flash-mtp-packed8k": 6 * (128 + 2) / 1024,  # 32 heads, values of 128, 16,384 tokens, 5 blocks + MTP
    "train-lfm2-moe-packed8k": 1 * (128 + 4) / 1024,  # 32 heads x 64, 32,768 tokens, 1 attention block
}


def _say_and_hold_the_estimate(capsys, cell: str, memory) -> None:
    """Print the step's state and temporaries beside the temporaries before PR 34, and hold the
    step to them and what its blocks keep of the attention kernel since PR 43: walking the routed
    rows in blocks must not cost a buffer (an estimate on both sides; the chip's reading is
    `hbm_peak_gib.train`, PERF.md)."""
    gib = 2.0**30
    with capsys.disabled():
        print(
            f"\n{cell} step for a described v5e: state {memory.argument_size_in_bytes / gib:.2f} GiB, temporaries "
            f"(estimate) {memory.temp_size_in_bytes / gib:.3f} GiB, before PR 34 {TEMPORARIES_BEFORE_THE_WALKS[cell]:.3f} GiB "
            f"+ {KERNEL_RESIDUALS_KEPT[cell]:.3f} GiB of kernel residuals kept"
        )
    assert memory.temp_size_in_bytes / gib <= TEMPORARIES_BEFORE_THE_WALKS[cell] + KERNEL_RESIDUALS_KEPT[cell] + 0.01


def _whole_buffer_row_movements(text: str, capacity: int, hidden: int) -> list:
    """The gathers that give, and the scatters that take, `capacity` rows of `hidden` in a
    compiled step's HLO (a scatter prints its operands by name: the updates' shape is looked up)."""
    line = r"^\s*(?:ROOT )?(%[\w.\-]+) = (\w+\[[\d,]*\])"
    shape_of = dict(re.findall(line, text, flags=re.M))
    found = []
    for name, shape, op, operands in re.findall(line + r"\S* (gather|scatter)\(([^)]*)\)", text, flags=re.M):
        rows = shape if op == "gather" else shape_of[operands.split(", ")[-1].strip()]
        if rows.endswith(f"[{capacity},{hidden}]"):
            found.append((name, op, rows))
    return found


def test_nemotron_h_tower_step_compiles_for_v5e_at_published_widths(v5e, capsys):
    """The benchmark cell's whole train step — the 9 layers MEMEM*EME of the `nemotron_h`
    tower at published widths, 2 packed rows of 8192 tokens, AdamW, as
    `benchmark/drivers/train_packed_tower.py` builds its arguments — for one described v5e:
    the chunked scan, the grouped products, splash at GQA 16:1 and the chunked loss on the
    untied head all lower, and the program fits the chip. The estimate of its temporaries is
    printed; it is an estimate (PERF.md section 6: it has differed from the chip's reading)."""
    compiled = _compiled_cell_step(v5e, "train-nemotron-tower-packed8k")
    memory, text = compiled.memory_analysis(), compiled.as_text()
    gib = 2.0**30
    _say_and_hold_the_estimate(capsys, "train-nemotron-tower-packed8k", memory)
    # on one TPU the experts' grouped products are megablox kernels (`ops/moe._share_grouped_product`): 4 layers
    # x (forward, replay, three in the backward) x 2 products, beside splash and the norms
    assert "ragged-dot" not in text and text.count('custom_call_target="tpu_custom_call"') >= 40
    # and the scans are the Pallas kernels (`ops/mamba2.mamba2_scan`): 4 M layers x (forward, replay, backward)
    kernels = [line.split(" = ")[0] for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    assert sum("mamba2_scan_fwd" in name for name in kernels) == 8, kernels
    assert sum("mamba2_scan_bwd" in name for name in kernels) == 4, kernels
    # 1 attention layer x (forward, dkv, dq) of splash: `full` keeps the forward's output and log-sum-exp, no replay
    count = lambda prefix: sum(name.strip().lstrip("%").startswith(prefix) for name in kernels)  # noqa: E731
    assert (count("splash_mha_fwd"), count("splash_mha_dkv"), count("splash_mha_dq")) == (1, 1, 1), kernels
    # the activation between the products walks its blocks in an XLA loop here (`ops/moe._share_activation`): 1856
    # does not fill whole lane rows, and Mosaic's layout would cost a copy of all 24,576 rows a side
    assert not any("moe_routed_row_blocks" in name for name in kernels), kernels
    assert 6.0 * gib < memory.argument_size_in_bytes < 6.5 * gib  # 667M parameters x 10 B of state
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 14.5 * gib


def test_joyai_flash_step_compiles_for_v5e_at_published_widths(v5e, capsys):
    """The cell `train-joyai-flash-mtp-packed8k`'s whole train step — a dense block, 4 expert
    blocks and the MTP module of `joyai_llm_flash` at published widths, 2 packed rows of 8192
    tokens, AdamW — for one described v5e: splash at scores over 192 and values of 128 (Mosaic
    takes the 192 as it is: no padding), the megablox products on gated banks and the chunked
    loss read twice all lower, and the program fits the chip (an estimate: the chip's reading is
    in PERF.md)."""
    compiled = _compiled_cell_step(v5e, "train-joyai-flash-mtp-packed8k")
    memory, text = compiled.memory_analysis(), compiled.as_text()
    gib = 2.0**30
    _say_and_hold_the_estimate(capsys, "train-joyai-flash-mtp-packed8k", memory)
    kernels = [line.split(" = ")[0] for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    count = lambda prefix: sum(name.strip().lstrip("%").startswith(prefix) for name in kernels)  # noqa: E731
    # 6 blocks x (forward, dkv, dq) of splash: no attention is left to XLA's products, and `full` remat keeps the
    # forward's output and log-sum-exp, so no block's replay launches it again
    assert (count("splash_mha_fwd"), count("splash_mha_dkv"), count("splash_mha_dq")) == (6, 6, 6), kernels
    # 5 layers of experts x (forward and its replay: 2 products each; backward: 2 for the rows, 2 for the banks)
    assert "ragged-dot" not in text and (count("gmm"), count("tgmm")) == (60, 20), kernels
    # and between the two products the activation's launch (PR 37; 1536 and 768 fill whole lane rows): 5 layers x
    # (forward, replay, backward) x the two paths `lax.cond` chooses from
    assert count("moe_routed_row_blocks") == 30, kernels
    assert 6.2 * gib < memory.argument_size_in_bytes < 6.5 * gib  # 680.4M parameters x 10 B of state
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 15.0 * gib


def test_lfm2_moe_step_compiles_for_v5e_at_published_widths(v5e, capsys):
    """The cell `train-lfm2-moe-packed8k`'s whole train step — a conv block with the dense MLP,
    an attention block and three conv blocks before experts, of `lfm2_moe` at published widths, 4
    packed rows of 8192 tokens, AdamW, built as `pretrain.main` builds it — for one described
    v5e: splash at head 64 and GQA 4:1 under the document block tables, the QK norms ahead of an
    XLA rotation (the fused rope+QKV kernel steps aside), the megablox products on gated banks of
    1536 with no shared expert beside them, and the chunked loss on the tied table all lower, and
    the program fits the chip (an estimate: the chip's reading is in PERF.md)."""
    compiled = _compiled_cell_step(v5e, "train-lfm2-moe-packed8k")
    memory, text = compiled.memory_analysis(), compiled.as_text()
    gib = 2.0**30
    _say_and_hold_the_estimate(capsys, "train-lfm2-moe-packed8k", memory)
    # PR 34: the experts' gather, weighted scatter-add and their transposes walk blocks of rows and stop at the
    # last routed one: nothing moves the `capacity` of 65,536 rows (four times the even share of 4 x 8192 tokens
    # x 4 slots x 8 of 64 experts) at once any more, where the step before held 40 such gathers and 16 scatters
    assert not _whole_buffer_row_movements(text, 65536, 2048)
    # 4 layers x (forward, its replay, backward) x (a gather, a scatter-add) of the 2048 rows a loop step takes
    assert len(_whole_buffer_row_movements(text, 2048, 2048)) >= 24
    kernels = [line.split(" = ")[0] for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    count = lambda prefix: sum(name.strip().lstrip("%").startswith(prefix) for name in kernels)  # noqa: E731
    # 1 attention block x (forward, dkv, dq) of splash: `full` remat keeps the forward's output and log-sum-exp
    assert (count("splash_mha_fwd"), count("splash_mha_dkv"), count("splash_mha_dq")) == (1, 1, 1), kernels
    # 4 layers of experts x (forward and its replay: 2 products each; backward: 2 for the rows, 2 for the banks)
    assert "ragged-dot" not in text and (count("gmm"), count("tgmm")) == (48, 16), kernels
    # PR 37: the SwiGLU between the two products is one elementwise launch a pass that is handed the routed rows
    # (`ops/pallas/moe.routed_row_blocks`: 3072 and 1536 fill whole lane rows): 4 layers x (forward, replay,
    # backward) in each of the two paths `lax.cond` chooses from (all rows at once; in chunks of `capacity`)
    assert count("moe_routed_row_blocks") == 24, kernels
    assert not any("rope_qkv" in name for name in kernels), kernels  # the norms sit before the rotation: XLA's form
    assert 4.3 * gib < memory.argument_size_in_bytes < 4.5 * gib  # 469.3M parameters x 10 B of state
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 15.5 * gib


def test_afmoe_step_compiles_for_v5e_at_published_widths_with_both_kinds_of_attention_through_splash(v5e, capsys):
    """The cell `train-trinity-mini-swa-packed16k`'s whole train step — four window layers and a
    full one of `afmoe` at published widths (32 query heads over 4 key/value heads of 128, a window
    of 2048, QK norms, the gate, four norms a block), a dense MLP and four layers of 128-way experts
    with a shared one, 2 packed rows of 16384 tokens, AdamW, built as `pretrain.main` builds it —
    for one described v5e: every attention layer lowers through splash on the block tables (the
    window layers under jax's local mask function), the fused rope+QKV kernel steps aside for the
    norms, the megablox products run gated banks of 1024, and the program fits the chip (an
    estimate: the chip's reading is in PERF.md)."""
    compiled = _compiled_cell_step(v5e, "train-trinity-mini-swa-packed16k")
    memory, text = compiled.memory_analysis(), compiled.as_text()
    gib = 2.0**30
    with capsys.disabled():
        print(
            f"\ntrain-trinity-mini-swa-packed16k step for a described v5e: state {memory.argument_size_in_bytes / gib:.2f} GiB, "
            f"temporaries (estimate) {memory.temp_size_in_bytes / gib:.3f} GiB"
        )
    kernels = [line.split(" = ")[0] for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    count = lambda prefix: sum(name.strip().lstrip("%").startswith(prefix) for name in kernels)  # noqa: E731
    # 5 attention blocks x (forward, dkv, dq) of splash: no layer of either kind is left to XLA's products, and
    # `full` remat keeps the forward's output and log-sum-exp (5 x 260 MiB), so no block's replay launches it again
    assert (count("splash_mha_fwd"), count("splash_mha_dkv"), count("splash_mha_dq")) == (5, 5, 5), kernels
    # 4 layers of experts: the grouped products and the activation's launch between them
    assert "ragged-dot" not in text and count("gmm") >= 48 and count("tgmm") == 16 and count("moe_routed_row_blocks") >= 24, kernels
    assert not any("rope_qkv" in name for name in kernels), kernels  # the norms sit before the rotation: XLA's form
    assert not _whole_buffer_row_movements(text, 8 * 32768, 2048)  # the experts' rows are walked in blocks, never moved whole
    assert 4.6 * gib < memory.argument_size_in_bytes < 4.8 * gib  # 504.1M parameters x 10 B of state
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 15.0 * gib  # the issue's bound for 2 rows a step


def test_ouro_step_compiles_for_v5e_at_published_widths_with_one_copy_of_the_stack(v5e, capsys):
    """The cell `train-ouro-loop4-packed8k`'s whole train step — eight sandwich-normed blocks of
    `ouro` at published widths run four times over shared weights, 2 packed rows of 8192 tokens,
    the gate, the head read over the four passes' rows stacked, AdamW — for one described v5e. The
    passes are one scan: the program holds ONE copy of each block's kernels (8 blocks x forward and
    its replay, dkv, dq of splash; four copies would read 64 / 32 / 32: a stack that applies its
    blocks more than once a step keeps nothing under `full`, the replay stays), and it fits the
    chip (an estimate: the chip's reading is in PERF.md)."""
    compiled = _compiled_cell_step(v5e, "train-ouro-loop4-packed8k")
    memory, text = compiled.memory_analysis(), compiled.as_text()
    gib = 2.0**30
    with capsys.disabled():
        print(
            f"\ntrain-ouro-loop4-packed8k step for a described v5e: state {memory.argument_size_in_bytes / gib:.2f} GiB, "
            f"temporaries (estimate) {memory.temp_size_in_bytes / gib:.3f} GiB"
        )
    kernels = [line.split(" = ")[0] for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    count = lambda prefix: sum(name.strip().lstrip("%").startswith(prefix) for name in kernels)  # noqa: E731
    assert (count("splash_mha_fwd"), count("splash_mha_dkv"), count("splash_mha_dq")) == (16, 8, 8), kernels
    assert 5.6 * gib < memory.argument_size_in_bytes < 5.9 * gib  # 612.5M parameters x 10 B of state
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 15.5 * gib
