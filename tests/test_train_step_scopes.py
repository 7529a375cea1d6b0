"""The train step names its phases (docs/OBSERVABILITY.md "Phases of the train step").

Lowers `make_train_step` for a tiny scanned `gpt_dolomite` with the chunked loss and reads
the framework names of the lowered operations (`jit(train_step)/.../op`, what a profile
calls `tf_op`): every phase scope is there, every scan sits in a scope of its own, the
head's three matmuls are under `head_loss` (`ce_block`: the logits, computed once, and the
hidden and table gradients the differentiated forward forms over them — PR 39; the per-token
rule's `ce_chunk` and `ce_tile` are held by the looped model's test), and no matmul is left
with JAX's bare `transpose(jvp())` — a backward rule or scan body with no name of its own.
Scopes are metadata: the compile-cache key strips them, so the compiled program is what it was.
"""

import re

import jax
import jax.numpy as jnp
import optax
import pytest
from flax import linen as nn

from dolomite_engine_tpu.models import config_from_dict
from dolomite_engine_tpu.models.gpt_dolomite import GPTDolomiteForCausalLM
from dolomite_engine_tpu.train_utils import TrainState, make_train_step
from tests.models.family_contract import FAMILIES


def _lowered(accumulation: int = 1, collect_health: bool = False, scan_layers: bool = True):
    config = config_from_dict(
        dict(
            model_type="gpt_dolomite", vocab_size=256, n_positions=64, n_embd=32, n_layer=4,
            n_head=4, num_key_value_heads=2, attention_head_type="gqa",
            position_embedding_type="rope", activation_function="swiglu",
            normalization_function="rmsnorm", add_bias=False, resid_pdrop=0.0,
            embd_pdrop=0.0, attn_pdrop=0.0, bos_token_id=0, eos_token_id=1, pad_token_id=2,
            tie_word_embeddings=True, fused_lm_head_loss=True, loss_chunk_size=16,
            z_loss_coef=1e-4,
        )
    )
    model = GPTDolomiteForCausalLM(
        config=config, scan_layers=scan_layers, checkpoint_every=2,
        checkpoint_policy="save_dots", dtype=jnp.bfloat16,
    )
    params = nn.unbox(model.init(jax.random.PRNGKey(0), jnp.zeros((1, 32), jnp.int32))["params"])
    optimizer = optax.adamw(1e-3)
    state = TrainState(
        step=jnp.zeros((), jnp.int32), params=params, opt_state=optimizer.init(params)
    )

    def loss_fn(params, micro, rng):
        return model.apply({"params": params}, micro["text"], compute_loss=True).loss

    step = make_train_step(
        loss_fn, optimizer, gradient_accumulation_steps=accumulation, skip_nonfinite=True,
        collect_health=collect_health,
    )
    batch = {"text": jnp.zeros((accumulation, 1, 32), jnp.int32)}
    return jax.jit(step).lower(state, batch, jax.random.PRNGKey(0))


def _operation_names(lowered) -> list:
    """[(stablehlo op, framework name)] of every operation with a named location. Inside a
    function the lowering made (a scan's body is one: `closed_call`) names are relative to
    the call; the call site's prefix is added when the program is compiled."""
    text = lowered.as_text(debug_info=True)
    named = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    found, open_regions = [], []  # an operation with regions prints its location after them
    for line in text.splitlines():
        indent = len(line) - len(line.lstrip())
        op = re.search(r'= "?stablehlo\.(\w+)', line)
        ref = re.search(r"loc\((#loc\d+)\)\s*$", line)
        if op and ref is None:
            open_regions.append((indent, op.group(1)))
            continue
        if op is None and ref and open_regions and open_regions[-1][0] == indent and line.lstrip().startswith("}"):
            op_name = open_regions.pop()[1]
        elif op and ref:
            op_name = op.group(1)
        else:
            continue
        if ref.group(1) in named:
            found.append((op_name, named[ref.group(1)]))
    return found


@pytest.fixture(scope="module")
def names():
    return _operation_names(_lowered())


@pytest.mark.parametrize(
    "scope", ["embed", "blocks", "final_norm", "head_loss", "loss_chunks", "grad_clip", "optimizer"]
)
def test_phase_scope_is_in_the_lowered_step(names, scope):
    assert any(scope in name.split("/") for _, name in names), scope


def test_every_scan_and_the_cond_sit_in_a_scope_of_their_own(names):
    whiles = sorted({name for op, name in names if op == "while"})
    # the layer scan, forward and backward; the head's one block of kept logits is no loop
    # (more tokens than a block keeps would walk `head_loss/loss_chunks/token_blocks/while`)
    assert len(whiles) == 2
    for name in whiles:
        assert "/blocks/while" in name and "/head_loss/" not in name, name
    assert sum("transpose(" in name for name in whiles) == 1
    conds = {name for op, name in names if op == "case"}
    assert conds and all(name.endswith("optimizer/cond") for name in conds), conds


def test_the_loss_backward_matmuls_are_under_head_loss(names):
    # the summed rule's differentiated forward forms the gradients where it forms the logits:
    # THREE matmuls under head_loss/loss_chunks, in the block's body (`ce_block`), told apart
    # by name — the logits (once: the parent's `ce_chunk` and `ce_tile/logits` were two) and
    # the two gradients' — and none in the backward pass, whose rule only scales
    dots = sorted(name for op, name in names if op == "dot_general" and "/head_loss/" in name)
    assert [name.split("/head_loss/")[1] for name in dots] == [
        "loss_chunks/ce_block/grad_hidden/dot_general",
        "loss_chunks/ce_block/grad_table/dot_general",
        "loss_chunks/ce_block/logits/dot_general",
    ]
    assert not [name for name in dots if "transpose(" in name]
    # the rule differentiates nothing and replays nothing
    assert not [name for _, name in names if "ce_chunk" in name or "ce_tile" in name]
    # ... and what the backward pass does under head_loss is elementwise: the scaling
    backward = {op for op, name in names if "/head_loss/" in name and "transpose(" in name}
    assert "multiply" in backward and not backward & {"dot_general", "while", "exponential", "reduce"}, backward


def test_no_matmul_is_left_without_an_owner(names):
    dots = [name for op, name in names if op == "dot_general"]
    assert len(dots) >= 8
    bare = [n for n in dots if n in ("dot_general", "jvp()/dot_general", "transpose(jvp())/dot_general")]
    assert not bare, bare
    for name in dots:
        assert "ce_block" in name or "h_scan" in name, name


def test_accumulation_and_health_have_scopes_too():
    names = _operation_names(_lowered(accumulation=2, collect_health=True))
    whiles = {name for op, name in names if op == "while"}
    assert any(name.endswith("accumulate/while") for name in whiles), whiles
    assert any("health" in name.split("/") for _, name in names)


def test_the_unrolled_model_carries_the_same_phases():
    names = _operation_names(_lowered(scan_layers=False))
    for scope in ("embed", "blocks", "final_norm", "head_loss", "optimizer"):
        assert any(scope in name.split("/") for _, name in names), scope
    dots = [
        name for op, name in names
        if op == "dot_general" and "ce_block" not in name
    ]
    assert dots and all("/blocks/" in name for name in dots), dots[:3]


def test_scopes_are_debug_locations_only():
    """Names live in debug locations alone: the lowering printed without them names no phase."""
    text = _lowered().as_text()
    assert "head_loss" not in text and "blocks" not in text


def test_joyai_flash_names_its_layers_and_both_passes_through_the_head():
    """`joyai_llm_flash`'s lowered train step carries the scopes docs/OBSERVABILITY.md lists:
    latent attention's, the dense MLP's, the experts', and the multi-token-prediction module
    under `blocks/mtp` and `head_loss/mtp/mtp_head_loss` — forward and backward — so that
    `benchmark/phases.py` counts the module with the blocks and the head."""
    from dolomite_engine_tpu.models import get_model_class
    CFG = FAMILIES["joyai_llm_flash"].cfg

    model = get_model_class("joyai_llm_flash")(config=config_from_dict(CFG), checkpoint_every=1, dtype=jnp.bfloat16)
    ids = jnp.zeros((1, 32), jnp.int32)
    params = nn.unbox(model.init(jax.random.PRNGKey(0), ids)["params"])
    assert "mtp" in params["transformer"]  # the module's parameters exist whatever the first call asked for
    optimizer = optax.adamw(1e-3)
    state = TrainState(step=jnp.zeros((), jnp.int32), params=params, opt_state=optimizer.init(params))

    def loss_fn(params, micro, rng):
        out = model.apply({"params": params}, micro["text"], compute_loss=True)
        return out.loss, out.counters

    lowered = jax.jit(make_train_step(loss_fn, optimizer, skip_nonfinite=True, has_aux=True)).lower(
        state, {"text": jnp.zeros((1, 1, 32), jnp.int32)}, jax.random.PRNGKey(0)
    )
    names = [name for _, name in _operation_names(lowered)]
    dots = [name for op, name in _operation_names(lowered) if op == "dot_general"]
    for scope in (
        "mla_q_down", "mla_q_up", "mla_kv_down", "mla_kv_up", "mla_rope", "mla_out_proj", "dense_mlp",
        "moe_router", "moe_dispatch", "moe_experts", "moe_shared_expert", "moe_combine", "mtp_combine", "mtp_final_norm",
    ):
        assert any(f"/{scope}/" in name and "transpose(" not in name for name in names), scope
        assert any(f"/{scope}/" in name and "transpose(" in name for name in names), scope
    in_blocks = [name for name in names if "/blocks/" in name]
    assert any("/blocks/mtp/" in name and "/latent_attention/" in name for name in in_blocks)
    assert any("/blocks/mtp/" in name and "/moe/" in name for name in in_blocks)
    # the head's two passes, three matmuls each (PR 39: the logits once), told apart by scope
    in_head = sorted(name.split("/head_loss/")[1] for name in dots if "/head_loss/" in name)
    products = [f"loss_chunks/ce_block/{product}/dot_general" for product in ("grad_hidden", "grad_table", "logits")]
    assert in_head == products + [f"mtp/mtp_head_loss/{name}" for name in products], in_head
    assert not [name for op, name in _operation_names(lowered) if op == "while" and "head_loss" in name]
    # no matmul of the blocks outside a layer's scope
    for name in dots:
        if "/blocks/" in name:
            assert re.search(r"/(latent_attention|dense_mlp|moe|mtp_combine)/", name), name


def test_lfm2_moe_names_its_operators_and_its_experts_without_a_shared_one():
    """`lfm2_moe`'s lowered train step carries the scopes docs/OBSERVABILITY.md lists — the short
    convolution's three, attention with `qk_norm` inside it, the dense MLP's, the experts' four —
    forward and backward, has no `moe_shared_expert` anywhere, and leaves no matmul of the blocks
    outside an operator's or a feed-forward's scope."""
    from dolomite_engine_tpu.models import get_model_class
    CFG = FAMILIES["lfm2_moe"].cfg

    model = get_model_class("lfm2_moe")(config=config_from_dict(CFG), checkpoint_every=1, dtype=jnp.bfloat16)
    ids = jnp.zeros((1, 32), jnp.int32)
    params = nn.unbox(model.init(jax.random.PRNGKey(0), ids)["params"])
    optimizer = optax.adamw(1e-3)
    state = TrainState(step=jnp.zeros((), jnp.int32), params=params, opt_state=optimizer.init(params))

    def loss_fn(params, micro, rng):
        out = model.apply({"params": params}, micro["text"], compute_loss=True)
        return out.loss, out.counters

    lowered = jax.jit(make_train_step(loss_fn, optimizer, skip_nonfinite=True, has_aux=True)).lower(
        state, {"text": jnp.zeros((1, 1, 32), jnp.int32)}, jax.random.PRNGKey(0)
    )
    names = [name for _, name in _operation_names(lowered)]
    dots = [name for op, name in _operation_names(lowered) if op == "dot_general"]
    for scope in (
        "short_conv_in_proj", "short_conv_gates_taps", "short_conv_out_proj", "qk_norm", "dense_mlp",
        "moe_router", "moe_dispatch", "moe_experts", "moe_combine",
    ):
        assert any(f"/{scope}/" in name and "transpose(" not in name for name in names), scope
        assert any(f"/{scope}/" in name and "transpose(" in name for name in names), scope
    assert not any("moe_shared_expert" in name for name in names)
    assert all("/attention/" in name for name in names if "/qk_norm/" in name)
    assert all("/short_conv/" in name for name in names if "/short_conv_gates_taps/" in name)
    # the gates and the taps hold no matmul: the memory-bound part
    assert not any("/short_conv_gates_taps/" in name for name in dots)
    for name in dots:
        if "/blocks/" in name:
            assert re.search(r"/(short_conv|attention|dense_mlp|moe)/", name), name


def test_ouro_names_the_pass_its_norms_the_gate_and_the_weighting():
    """`ouro`'s lowered train step carries the scopes docs/OBSERVABILITY.md lists — `blocks/pass`
    (ONE name for the loop's body: the passes are the scan's iterations), `block_norms` inside it,
    `exit_gate`, `head_loss` with `pass_weighting` inside it — forward and backward; every matmul of
    the blocks sits inside the scan over passes; and the head is read by one pair of scans."""
    from dolomite_engine_tpu.models import get_model_class
    CFG = FAMILIES["ouro"].cfg

    model = get_model_class("ouro")(config=config_from_dict(CFG), checkpoint_every=1, dtype=jnp.bfloat16)
    ids = jnp.zeros((1, 32), jnp.int32)
    params = nn.unbox(model.init(jax.random.PRNGKey(0), ids)["params"])
    optimizer = optax.adamw(1e-3)
    state = TrainState(step=jnp.zeros((), jnp.int32), params=params, opt_state=optimizer.init(params))

    def loss_fn(params, micro, rng):
        out = model.apply({"params": params}, micro["text"], compute_loss=True)
        return out.loss, out.counters

    lowered = jax.jit(make_train_step(loss_fn, optimizer, skip_nonfinite=True, has_aux=True)).lower(
        state, {"text": jnp.zeros((1, 1, 32), jnp.int32)}, jax.random.PRNGKey(0)
    )
    names = [name for _, name in _operation_names(lowered)]
    dots = [name for op, name in _operation_names(lowered) if op == "dot_general"]
    for scope in ("exit_gate", "pass_weighting"):
        assert any(f"/{scope}/" in name and "transpose(" not in name for name in names), scope
        assert any(f"/{scope}/" in name and "transpose(" in name for name in names), scope
    # the loop's body is a function of its own in the lowering (its names start again at the
    # scan's module: the compiled program's carry the whole path, `.../blocks/while/body/closed_call/stack/pass/...`);
    # the backward pass holds the blocks' replays under `checkpoint/rematted_computation`
    for scope in ("pass", "block_norms"):
        assert any(f"/{scope}/" in name and "rematted_computation" not in name for name in names), scope
        assert any(f"/{scope}/" in name and "rematted_computation" in name for name in names), scope
    assert all("/pass/" in name for name in names if "/block_norms/" in name)
    assert all("/head_loss/" in name for name in names if "/pass_weighting/" in name)
    assert not any("/pass_weighting/" in name for name in dots)  # the weighting holds no matmul
    # every matmul of a block inside the loop's body, under the one name
    block_dots = [name for name in dots if "/attn/" in name or "/mlp/" in name]
    assert block_dots and all("/pass/" in name for name in block_dots), block_dots
    assert any(op == "while" and "/blocks/" in name for op, name in _operation_names(lowered))
    # the head: one forward scan over chunks, and the backward rule's
    loss_scans = {name for op, name in _operation_names(lowered) if op == "while" and "head_loss" in name}
    assert sum("transpose(" not in name for name in loss_scans) == 1, loss_scans


def test_afmoe_names_its_two_kinds_of_attention_the_gate_and_its_norms():
    """`afmoe`'s lowered train step carries the scopes docs/OBSERVABILITY.md lists — `attention`
    and inside it `attention_window` or `attention_full` by the layer's kind, `qk_norm` and
    `attention_gate` inside those, `dense_mlp`, the experts' five, `block_norms` — forward and
    backward, and leaves no matmul of the blocks outside a layer's scope."""
    from dolomite_engine_tpu.models import get_model_class
    CFG = FAMILIES["afmoe"].cfg

    model = get_model_class("afmoe")(config=config_from_dict(CFG), checkpoint_every=1, dtype=jnp.bfloat16)
    ids = jnp.zeros((1, 32), jnp.int32)
    params = nn.unbox(model.init(jax.random.PRNGKey(0), ids)["params"])
    optimizer = optax.adamw(1e-3)
    state = TrainState(step=jnp.zeros((), jnp.int32), params=params, opt_state=optimizer.init(params))

    def loss_fn(params, micro, rng):
        out = model.apply({"params": params}, micro["text"], compute_loss=True)
        return out.loss, out.counters

    lowered = jax.jit(make_train_step(loss_fn, optimizer, skip_nonfinite=True, has_aux=True)).lower(
        state, {"text": jnp.zeros((1, 1, 32), jnp.int32)}, jax.random.PRNGKey(0)
    )
    names = [name for _, name in _operation_names(lowered)]
    dots = [name for op, name in _operation_names(lowered) if op == "dot_general"]
    for scope in (
        "attention_window", "attention_full", "qk_norm", "attention_gate", "dense_mlp", "block_norms",
        "moe_router", "moe_dispatch", "moe_experts", "moe_shared_expert", "moe_combine",
    ):
        assert any(f"/{scope}/" in name and "transpose(" not in name for name in names), scope
        assert any(f"/{scope}/" in name and "transpose(" in name for name in names), scope
    for inner in ("attention_window", "attention_full"):
        assert all("/attention/" in name for name in names if f"/{inner}/" in name)
    for inner in ("qk_norm", "attention_gate"):
        assert all(re.search(r"/attention/(attention_window|attention_full)/", name) for name in names if f"/{inner}/" in name)
    # four of the five blocks are window layers (h_0, h_1, h_3, h_4), one is full (h_2): a kind's scope holds its blocks only
    assert {re.search(r"/(h_\d)/", name).group(1) for name in names if "/attention_full/" in name and re.search(r"/(h_\d)/", name)} == {"h_2"}
    assert {re.search(r"/(h_\d)/", name).group(1) for name in names if "/attention_window/" in name and re.search(r"/(h_\d)/", name)} == {"h_0", "h_1", "h_3", "h_4"}
    # the gate is a projection as wide as the heads' output and an elementwise pass; the norms hold no matmul
    assert any("/attention_gate/" in name for name in dots) and not any("/block_norms/" in name or "/qk_norm/" in name for name in dots)
    for name in dots:
        if "/blocks/" in name:
            assert re.search(r"/(attention|dense_mlp|moe)/", name), name
