"""Analytic TFLOPs/MFU accounting (train_utils.get_model_tflops).

Reference formula: train_utils.py:197-236 — attn = 4bsh(h(1+k/n)+s), mlp = 4bshf (+2bshf
GLU), lm_head = 6bshv, bwd = 2x fwd, +1x fwd per checkpointed block. The reference predates
its MoE models and always counts one dense MLP; here the MoE families count their real
MLP FLOPs (dense configs stay bit-identical)."""

from dolomite_engine_tpu.models.config import CommonConfig, DenseMoEConfig, MoEConfig
from dolomite_engine_tpu.train_utils import get_model_tflops

_COMMON = dict(
    vocab_size=1024,
    n_positions=128,
    n_embd=256,
    n_layer=2,
    n_head=4,
    num_key_value_heads=4,
    attention_head_type="mha",
    activation_function="swiglu",
)


def _pieces(b, s, config):
    h, f, n, k, v, l = (
        config.n_embd, config.n_inner, config.n_head,
        config.num_key_value_heads, config.vocab_size, config.n_layer,
    )
    attn = 4 * b * s * h * (h * (1 + k / n) + s)
    mlp = 6 * b * s * h * f  # 4 + 2 (GLU)
    lm_head = 6 * b * s * h * v
    return attn, mlp, lm_head, l


def test_dense_matches_reference_formula():
    config = CommonConfig(**_COMMON)
    b, s = 4, 128
    attn, mlp, lm_head, l = _pieces(b, s, config)
    assert get_model_tflops(config, b, s) == (3 * l * (attn + mlp) + lm_head) / 1e12


def test_moe_counts_active_experts():
    """moe_dolomite: num_experts_per_tok expert MLPs per token."""
    config = MoEConfig(**_COMMON, num_experts=8, num_experts_per_tok=2)
    b, s = 4, 128
    attn, mlp, lm_head, l = _pieces(b, s, config)
    assert get_model_tflops(config, b, s) == (3 * l * (attn + 2 * mlp) + lm_head) / 1e12


def test_dense_moe_counts_wide_mlp():
    """dense_moe runs ONE wide MLP of num_experts * n_inner for every token
    (models/dense_moe.py:74) -> mlp term scales by num_experts."""
    config = DenseMoEConfig(**_COMMON, num_experts=4)
    b, s = 4, 128
    attn, mlp, lm_head, l = _pieces(b, s, config)
    assert get_model_tflops(config, b, s) == (3 * l * (attn + 4 * mlp) + lm_head) / 1e12


def test_checkpointing_adds_recompute_fraction():
    config = CommonConfig(**_COMMON)
    b, s = 4, 128
    attn, mlp, lm_head, l = _pieces(b, s, config)
    got = get_model_tflops(
        config, b, s, gradient_checkpointing_method="block",
        gradient_checkpointing_args={"checkpoint_every": 2},
    )
    fwd = l * (attn + mlp)
    assert got == (3 * fwd + 0.5 * fwd + lm_head) / 1e12


def test_recompute_fraction_varies_per_policy():
    """The recompute term derives from the SELECTED policy (ISSUE 14 acceptance):
    full = one fwd per checkpointed block, save_dots/offload_dots ~ 0, and
    save_attention_out discounts the saved out-projection dot."""
    config = CommonConfig(**_COMMON)
    b, s = 4, 128
    attn, mlp, lm_head, l = _pieces(b, s, config)
    h = config.n_embd
    fwd = l * (attn + mlp)
    base = 3 * fwd + lm_head

    def tflops(policy):
        return get_model_tflops(
            config, b, s, gradient_checkpointing_method="block",
            gradient_checkpointing_args={"checkpoint_every": 2, "policy": policy},
        )

    assert tflops("full") == (base + 0.5 * fwd) / 1e12
    assert tflops("save_dots") == base / 1e12
    assert tflops("offload_dots") == base / 1e12
    assert tflops("save_attention_out") == (
        base + 0.5 * (fwd - l * 4 * b * s * h * h)
    ) / 1e12
    # legacy raw jax names keep working through the same classifier
    assert (
        get_model_tflops(
            config, b, s, "block",
            {"checkpoint_every": 2, "checkpoint_policy": "dots_saveable"},
        )
        == base / 1e12
    )


def test_none_method_with_args_counts_recompute():
    """Standing bug (ISSUE 14 satellite): gradient_checkpointing_args WITHOUT a method
    used to report zero recompute — remat is active whenever args were given."""
    config = CommonConfig(**_COMMON)
    b, s = 4, 128
    attn, mlp, lm_head, l = _pieces(b, s, config)
    fwd = l * (attn + mlp)
    got = get_model_tflops(config, b, s, None, {"checkpoint_every": 2})
    assert got == (3 * fwd + 0.5 * fwd + lm_head) / 1e12
    # the legacy block_frequency spelling resolves too (old reader defaulted it to 1)
    got = get_model_tflops(config, b, s, None, {"block_frequency": 2})
    assert got == (3 * fwd + 0.5 * fwd + lm_head) / 1e12
    assert get_model_tflops(config, b, s, None, None) == (3 * fwd + lm_head) / 1e12


def test_estimate_remat_activation_bytes_orders_policies():
    """The activation estimate must order the policies the way the policies order
    memory: save_dots > save_attention_out > full on device; offload_dots parks the
    dots host-side and matches full on device."""
    from dolomite_engine_tpu.train_utils import estimate_remat_activation_bytes

    config = CommonConfig(**_COMMON)

    def est(policy):
        return estimate_remat_activation_bytes(
            config, 4, 128, "block", {"checkpoint_every": 1, "policy": policy}
        )

    full, dots, attn_out, offload = map(
        est, ("full", "save_dots", "save_attention_out", "offload_dots")
    )
    assert full["delta_vs_full_bytes"] == 0.0
    assert dots["activation_bytes_per_replica"] > attn_out["activation_bytes_per_replica"]
    assert attn_out["activation_bytes_per_replica"] > full["activation_bytes_per_replica"]
    assert offload["activation_bytes_per_replica"] == full["activation_bytes_per_replica"]
    assert offload["host_offload_bytes_per_replica"] > 0
    assert attn_out["policy"] == "save_attention_out"


def test_val_group_names_from_weighted_split_paths():
    """Named validation groups (reference pretrain.py:96-98): report names come from the
    val_weighted_split_paths group keys; absent structure -> None (numeric fallback)."""
    from types import SimpleNamespace

    from dolomite_engine_tpu.pretrain import get_group_names

    paths = [
        {"books": [{"path": "p1", "split": "98,1,1", "weight": 1.0}]},
        {"web": [{"path": "p2", "split": "98,1,1", "weight": 1.0}]},
    ]
    args = SimpleNamespace(
        datasets=[SimpleNamespace(class_args={"val_weighted_split_paths": paths})]
    )
    assert get_group_names(args, "val_weighted_split_paths") == ["books", "web"]
    assert get_group_names(args, "test_weighted_split_paths") is None
    assert get_group_names(SimpleNamespace(datasets=[]), "val_weighted_split_paths") is None


def test_a_family_that_counts_its_own_blocks_is_asked_for_them():
    """`nemotron_h`'s block is one mixer, not attention + MLP: `get_model_tflops` takes the
    blocks' forward FLOPs from the config's `forward_block_flops` (it tests no model's
    name), adds the backward as twice that, one more forward per re-computed block, and
    the head; here each kind of layer against a hand sum."""
    from dolomite_engine_tpu.models.config import NemotronHConfig

    config = NemotronHConfig(
        vocab_size=512, n_positions=64, n_embd=32, n_layer=3, hybrid_override_pattern="ME*", n_head=4,
        num_key_value_heads=2, attention_head_dim=8, mamba_num_heads=4, mamba_head_dim=16, mamba_n_groups=2,
        ssm_state_size=8, num_experts=16, num_experts_per_tok=2, experts_held=[4, 4], moe_intermediate_size=24,
        moe_shared_expert_intermediate_size=48,
    )
    b, s, h = 2, 64, 32
    mamba = 2 * b * s * (h * (64 + (64 + 2 * 2 * 8) + 4) + 64 * h)  # in-projection [z | xBC | dt], out-projection
    experts = 2 * b * s * (h * 16 + 2 * h * 48 + 2 * (4 / 16) * 2 * h * 24)  # router, shared, the held quarter of 2 slots
    attention = 2 * b * s * (h * (4 + 2 * 2) * 8 + 4 * 8 * h) + 4 * b * s * s * 4 * 8
    assert config.forward_block_flops(b, s) == mamba + experts + attention
    head = 6 * b * s * h * 512
    assert get_model_tflops(config, b, s) == (3 * (mamba + experts + attention) + head) / 1e12
    full = get_model_tflops(config, b, s, "block", {"checkpoint_every": 1, "policy": "full"})
    assert full == (4 * (mamba + experts + attention) + head) / 1e12
