"""`nemotron_h` (models/nemotron_h.py) at a small size on the CPU, against the plain reference
(`benchmark/reference/nemotron_h_tower.py`) on seeded weights. The family's contract — registered,
the loss and every leaf's gradient, three AdamW steps through the trainer's own step, the shares of
an expert layer adding up to the reference's uncut layer, what the family refuses, the lowered step
— is `family_contract.py`'s; here is what is the tower's own: packed documents against the
documents run apart, and what the ``mamba2_scan_plan`` event says."""

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from dolomite_engine_tpu.models import config_from_dict, get_model_class

from .family_contract import FAMILIES, built, contract_tests, model_of

CFG = FAMILIES["nemotron_h"].cfg
DOCS = (10, 37, 17)
globals().update(contract_tests("nemotron_h"))


def test_the_expert_layer_is_the_one_the_expert_families_share():
    from dolomite_engine_tpu.models import nemotron_h, shared_expert_moe

    assert nemotron_h.SharedExpertMoE is shared_expert_moe.SharedExpertMoE


def test_a_packed_row_is_its_documents_run_apart():
    """State, convolution taps and attention all reset: the logits of a document inside a
    packed row are those of the document alone (`M`, `E` and `*` layers all in the pattern)."""
    model, _, params, _ = built("nemotron_h")
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, sum(DOCS)), 1, CFG["vocab_size"])
    seg = jnp.asarray(np.repeat([1, 2, 3], DOCS))[None]
    packed = model.apply({"params": params}, ids, segment_ids=seg).logits
    start = 0
    for length in DOCS:
        alone = model.apply({"params": params}, ids[:, start : start + length]).logits
        np.testing.assert_allclose(packed[:, start : start + length], alone, rtol=2e-4, atol=2e-4)
        start += length
    unreset = model.apply({"params": params}, ids).logits
    assert float(jnp.abs(unreset - packed)[:, DOCS[0] :].max()) > 1e-3


def _scan_plan_events(run, tmp_path):
    import json

    from dolomite_engine_tpu.utils.telemetry import Telemetry, install_telemetry, uninstall_telemetry

    sink = tmp_path / "t.jsonl"
    telemetry = Telemetry(sink_path=str(sink), rank=0)
    install_telemetry(telemetry)
    try:
        out = run()
    finally:
        uninstall_telemetry()
        telemetry.close()
    events = [json.loads(line) for line in sink.read_text().splitlines()]
    return out, [e for e in events if e["kind"] == "event" and e["event"] == "mamba2_scan_plan"]


def test_scan_plan_event_on_the_cpu_says_every_m_layer_took_the_jnp_form(tmp_path):
    """Once a traced model, however often it is traced: the two `M` layers of ``MEM*E``, both
    on the `jnp` form, because this is no TPU."""
    model, params = model_of("nemotron_h", checkpoint_every=1), built("nemotron_h")[2]
    ids = jnp.zeros((1, 32), jnp.int32)

    def run():
        loss = lambda p: jnp.sum(model.apply({"params": p}, ids).logits ** 2)  # noqa: E731
        jax.make_jaxpr(jax.grad(loss))(params)
        jax.make_jaxpr(jax.grad(loss))(params)

    _, (plan,) = _scan_plan_events(run, tmp_path)
    assert (plan["layers"], plan["kernel_layers"], plan["jnp_layers"]) == (2, [], [0, 1])
    assert plan["jnp_reasons"] == ["backend"] and plan["chunk"] == CFG["chunk_size"]
    assert plan["kernel_launches_per_layer_and_pass"] == 0 and plan["kept_bytes_per_layer"] == 0


def test_a_model_told_it_stands_on_a_tpu_runs_the_scan_s_kernel(tmp_path, monkeypatch):
    """Two `M` layers at the published head layout (heads of 64, groups of 8, state 128, chunk
    128), every layer re-computed in the backward pass: with the backend read as a TPU the
    scans go through the Pallas kernels (interpreted here) and the event says 2 of 2; loss and
    gradients are the `jnp` form's, a layer's replay and its backward rule included."""
    from dolomite_engine_tpu.utils import packages

    cfg = dict(
        CFG, n_layer=2, hybrid_override_pattern="MM", n_positions=256,
        mamba_num_heads=16, mamba_head_dim=64, mamba_n_groups=2, ssm_state_size=128, chunk_size=128,
    )
    model = get_model_class("nemotron_h")(config=config_from_dict(cfg), checkpoint_every=1)
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, 256), 1, cfg["vocab_size"])
    seg = jnp.asarray(np.repeat([1, 2, 3], [100, 28, 128]))[None]
    params = nn.unbox(model.init(jax.random.PRNGKey(0), ids)["params"])
    loss = lambda p: jnp.mean(model.apply({"params": p}, ids, segment_ids=seg).logits ** 2)  # noqa: E731
    reference_value, reference_grads = jax.value_and_grad(loss)(params)

    monkeypatch.setattr(packages, "pallas_interpret_mode", lambda: True)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    (value, grads), (plan,) = _scan_plan_events(lambda: jax.value_and_grad(loss)(params), tmp_path)
    assert (plan["layers"], plan["kernel_layers"], plan["jnp_layers"], plan["jnp_reasons"]) == (2, [0, 1], [], [])
    assert plan["kernel_launches_per_layer_and_pass"] == 1 and plan["chunk"] == 128
    assert plan["kept_state_bytes_per_layer"] == 2 * 128 * 16 * 64 * 4  # [1 row, 2 chunks, N, H*P] float32
    np.testing.assert_allclose(value, reference_value, rtol=1e-5)
    for (path, mine), ref in zip(jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(reference_grads)):
        np.testing.assert_allclose(mine, ref, rtol=2e-3, atol=2e-5 * float(jnp.abs(ref).max()), err_msg=str(path))
