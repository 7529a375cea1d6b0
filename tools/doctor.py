"""Pre-flight doctor: check a training config + mesh without running a train step.

    python tools/doctor.py --config configs/pretraining-examples/foo.yml [--mode training]

Builds the args tree, the model (abstract shapes only — no weights are materialized, no
checkpoint is read), the mesh, and the optimizer, then renders the same `model_report` the
train loops emit at startup (`dolomite_engine_tpu/utils/diagnostics.py`): per-parameter-group
counts/bytes, sharding spec per group, the per-device persistent-state HBM estimate vs the
detected device capacity, plus a best-effort forward-pass cost analysis from `jax.jit(...)
.lower(...)` when shapes are known. Run it on the machine type you will train on (or under
`XLA_FLAGS=--xla_force_host_platform_device_count=N` to emulate an N-device mesh on CPU) to
catch indivisible shardings, over-capacity states, and config typos before burning a pod
allocation on them.

Exit code: 0 on success (warnings included), 1 when the config/model/mesh cannot be built.
"""

from __future__ import annotations

import argparse
import os
import sys

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO_ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from telemetry_summary import format_model_report  # noqa: E402


def _forward_cost_analysis(model, abstract_params, args) -> dict | None:
    """Best-effort FLOPs/bytes of ONE forward micro-batch from the staged computation
    (the lowering-only perf signature, `utils/program_signature.py` — no compile, no
    execution). Pretraining only: the token-window shape is declared in the config;
    finetune batch shapes come from data."""
    import jax

    sequence_length = getattr(model, "sequence_length", None)
    micro_batch_size = getattr(model, "micro_batch_size", None)
    if not sequence_length or not micro_batch_size:
        return None
    try:
        import jax.numpy as jnp

        from dolomite_engine_tpu.utils.program_signature import capture_program_signature

        text = jax.ShapeDtypeStruct((micro_batch_size, sequence_length + 1), jnp.int32)
        sig = capture_program_signature(
            lambda params, tokens: model.loss(params, tokens, rngs=None, train=False),
            abstract_params,
            text,
            name="forward_loss",
            compile=False,
        )
        out = {k: v for k, v in sig.cost.items() if k in ("flops", "bytes_accessed")}
        return out or None
    except Exception as error:
        print(f"(cost analysis unavailable: {error!r})")
        return None


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--config", required=True, help="training YAML config to check")
    parser.add_argument(
        "--mode",
        default="training",
        choices=["training"],
        help="args mode (model introspection is a training-side concern)",
    )
    parsed = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from dolomite_engine_tpu.arguments import args_from_dict
    from dolomite_engine_tpu.distributed import (
        build_mesh_from_args,
        get_data_parallel_world_size,
        get_state_shardings,
    )
    from dolomite_engine_tpu.enums import Mode
    from dolomite_engine_tpu.finetune import build_optimizer_from_args
    from dolomite_engine_tpu.model_wrapper import get_model
    from dolomite_engine_tpu.train_utils import get_model_tflops
    from dolomite_engine_tpu.utils import load_yaml
    from dolomite_engine_tpu.utils.diagnostics import build_model_report

    from flax import linen as nn

    try:
        args = args_from_dict(load_yaml(parsed.config), Mode.training)
    except Exception as error:
        print(f"CONFIG ERROR: {error}", file=sys.stderr)
        return 1

    try:
        model = get_model(args, Mode.training)
    except Exception as error:
        print(f"MODEL ERROR: {error}", file=sys.stderr)
        return 1
    print(f"config OK: {parsed.config}")
    print(
        f"model OK: {model.model_type}, {model.num_parameters():,} parameters "
        f"(dtype {jnp.dtype(model.dtype).name})"
    )

    # mesh + shardings are best-effort: this host may have fewer devices than the target
    # pod (the report then shows unsharded sizes and says so)
    mesh = None
    try:
        mesh = build_mesh_from_args(args)
        print(f"mesh OK: {dict(zip(mesh.axis_names, mesh.devices.shape))}")
    except Exception as error:
        print(
            f"mesh UNAVAILABLE on this host ({jax.device_count()} device(s)): {error}\n"
            "  -> sharding/per-device numbers below assume a single device; re-run with "
            "XLA_FLAGS=--xla_force_host_platform_device_count=<pod devices> to emulate"
        )

    optimizer, _ = build_optimizer_from_args(args, model)

    abstract_params = model.abstract_params()
    params_tree = abstract_params
    opt_tree = jax.eval_shape(optimizer.init, abstract_params)
    if mesh is not None:
        try:
            abstract_state, shardings = get_state_shardings(model, optimizer, mesh)
            params_tree = jax.tree.map(
                lambda leaf, sharding: jax.ShapeDtypeStruct(
                    leaf.shape, leaf.dtype, sharding=sharding
                ),
                nn.unbox(abstract_state.params),
                shardings.params,
            )
            opt_tree = jax.tree.map(
                lambda leaf, sharding: jax.ShapeDtypeStruct(
                    leaf.shape, leaf.dtype, sharding=sharding
                ),
                nn.unbox(abstract_state.opt_state),
                shardings.opt_state,
            )
        except Exception as error:
            print(f"sharding derivation failed (report shows unsharded sizes): {error}")

    model_tflops = None
    remat = None
    sequence_length = getattr(model, "sequence_length", None)
    if args.training_parameters is not None and sequence_length:
        from dolomite_engine_tpu.ops.attention import splash_expected
        from dolomite_engine_tpu.train_utils import estimate_remat_activation_bytes

        model_tflops = get_model_tflops(
            model.config,
            batch_size=args.training_parameters.micro_batch_size
            * args.training_parameters.gradient_accumulation_steps,
            sequence_length=sequence_length,
            gradient_checkpointing_method=args.distributed_args.gradient_checkpointing_method,
            gradient_checkpointing_args=args.distributed_args.gradient_checkpointing_args,
        )
        # active remat policy + per-replica activation-HBM estimate vs `full`, next to
        # the state-HBM estimate — the pre-flight answer to "will activations fit, and
        # which policy knob moves them"
        remat = estimate_remat_activation_bytes(
            model.config,
            batch_size=args.training_parameters.micro_batch_size,
            sequence_length=sequence_length,
            gradient_checkpointing_method=args.distributed_args.gradient_checkpointing_method,
            gradient_checkpointing_args=args.distributed_args.gradient_checkpointing_args,
            dtype_bytes=jnp.dtype(model.dtype).itemsize,
            attention_kernel=splash_expected(model.attention_implementation),
        )

    report = build_model_report(
        params_tree,
        opt_state=opt_tree,
        model_tflops_per_step=model_tflops,
        cost_analysis=_forward_cost_analysis(model, abstract_params, args),
        remat=remat,
    )
    if mesh is not None and report.get("mesh") is None:
        report["mesh"] = {
            "axis_names": [str(n) for n in mesh.axis_names],
            "shape": [int(s) for s in mesh.devices.shape],
        }

    print()
    print("# model_report")
    print()
    print("\n".join(format_model_report(report)))

    if args.training_parameters is not None and sequence_length:
        dp_world = get_data_parallel_world_size(args)
        tokens_per_step = (
            args.training_parameters.micro_batch_size
            * args.training_parameters.gradient_accumulation_steps
            * dp_world
            * sequence_length
        )
        print()
        print(
            f"global batch: {tokens_per_step:,} tokens/step "
            f"(dp world {dp_world}, grad accum "
            f"{args.training_parameters.gradient_accumulation_steps})"
        )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
