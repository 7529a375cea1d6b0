"""Pod-scale sharding evidence without pod hardware: AOT-compile the FULL training step
over virtual CPU meshes of 8 -> 256 devices and report the collectives XLA inserted.

BASELINE.md lists "scaling efficiency 8->256 chips" as a metric with no reference number;
real multi-chip hardware is unavailable here, so this tool provides the strongest
chip-independent evidence: GSPMD partitions the identical program at every pod size in
SCALING.md's mesh shapes. The reported counts are whatever the CPU-backend SPMD partitioner
actually emitted — e.g. on this backend it phrases the ZeRO-3 grad reduction as
all-reduce(+slice) rather than reduce-scatter, and uses collective-permutes for internal
resharding even at sp=1 — so read the artifact, not assumptions, when citing the mix.

Each device count runs in a subprocess (JAX_PLATFORMS=cpu +
--xla_force_host_platform_device_count must be set before interpreter start). Writes one
JSON line per mesh to stdout; `--out SCALING_REPORT.json` collects them.

Usage: python tools/scaling_report.py [--out SCALING_REPORT.json]
"""

import argparse
import json
import os
import re
import shutil
import subprocess
import sys

# (n_devices, dp, fsdp, sp, tp) — SCALING.md's v5e-256 recipe is (1, 64, 1, 4); the smaller
# meshes are its 8- and 32-chip slices
MESHES = [
    (8, 1, 4, 1, 2),
    (32, 1, 16, 1, 2),
    (64, 1, 16, 1, 4),
    (256, 1, 64, 1, 4),
]

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "collective-permute", "all-to-all")


def _child(n: int, dp: int, fsdp: int, sp: int, tp: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from dolomite_engine_tpu.distributed import create_sharded_train_state
    from dolomite_engine_tpu.enums import LRDecaySchedule, Mode
    from dolomite_engine_tpu.model_wrapper.pretraining import ModelWrapperForPretraining
    from dolomite_engine_tpu.optimization import get_optimizer, get_scheduler
    from dolomite_engine_tpu.parallel.mesh import MeshManager, named_sharding
    from dolomite_engine_tpu.train_utils import make_train_step

    assert jax.device_count() == n, (jax.device_count(), n)
    seq = 256
    config = dict(
        model_type="gpt_dolomite",
        vocab_size=1024,
        n_positions=seq,
        n_embd=256,
        n_layer=2,
        n_head=8,
        num_key_value_heads=4,
        attention_head_type="gqa",
        position_embedding_type="rope",
        activation_function="swiglu",
        normalization_function="rmsnorm",
        add_bias=False,
        resid_pdrop=0.0,
        embd_pdrop=0.0,
        attn_pdrop=0.0,
        bos_token_id=0,
        eos_token_id=1,
        pad_token_id=2,
        fused_lm_head_loss=True,
        loss_chunk_size=128,
    )

    MeshManager(
        data_parallel_replication_world_size=dp,
        data_parallel_sharding_world_size=fsdp,
        sequence_parallel_size=sp,
        tensor_parallel_size=tp,
    )
    mesh = MeshManager.get_mesh()
    wrapper = ModelWrapperForPretraining(
        mode=Mode.training,
        pretrained_config=config,
        dtype="fp32",
        sequence_length=seq,
        tensor_parallel_word_embeddings=tp > 1,
        sequence_parallel=tp > 1,
        zero_stage=3,
    )
    sched = get_scheduler(2, 0, None, 10, LRDecaySchedule.cosine, 0.1, base_lr=1e-3)
    opt = get_optimizer(
        "TorchAdamW", {"weight_decay": 0.1, "betas": (0.9, 0.95), "eps": 1e-10}, sched
    )
    state, _ = create_sharded_train_state(wrapper, opt, mesh, jax.random.PRNGKey(0))

    def loss_fn(params, micro, rng):
        return wrapper.loss(params, micro["text"], train=True)

    step_fn = make_train_step(loss_fn, opt, gradient_accumulation_steps=2)
    rows = max(dp * fsdp, 8)
    tokens = np.zeros((2, rows, seq + 1), np.int32)

    import time

    with mesh:
        batch = {"text": jax.device_put(jnp.asarray(tokens), named_sharding(None, ("dp", "fsdp")))}
        t0 = time.perf_counter()
        lowered = jax.jit(step_fn, donate_argnums=0).lower(state, batch, jax.random.PRNGKey(1))
        compiled = lowered.compile()
        compile_s = time.perf_counter() - t0

    hlo = compiled.as_text()
    counts = {}
    for op in _COLLECTIVES:
        # count op INSTRUCTIONS (e.g. "all-reduce(" / "all-reduce-start("), not result-type
        # mentions; fusion names like "all-reduce-fusion" are excluded by the word boundary
        counts[op] = len(re.findall(rf"= \S+ {op}(?:-start)?\(", hlo))

    # per-device memory columns come from the shared perf-signature extraction
    # (utils/program_signature.py) — the same path tools/perf_ledger.py gates on
    from dolomite_engine_tpu.utils.program_signature import extract_signature

    sig = extract_signature(lowered, compiled, name=f"train_step[devices={n}]")

    # Evidence for the memory column: the largest PER-DEVICE buffers backing temp_size.
    # Parse the buffer-assignment dump (enabled by the parent via --xla_dump_to) so a
    # surprising peak_bytes can be attributed to a specific HLO value, not guessed at.
    top_buffers = []
    dump_dir = os.environ.get("_SCALING_REPORT_DUMP")
    if dump_dir:
        import glob as _glob

        paths = _glob.glob(os.path.join(dump_dir, "*train_step*buffer-assignment*.txt"))
        sized = []
        if paths:
            with open(sorted(paths)[-1]) as f:
                for line in f:
                    m = re.match(r"\s*allocation \d+: size (\d+)", line)
                    if m:
                        sized.append((int(m.group(1)), " ".join(line.split())[:160]))
        sized.sort(key=lambda x: -x[0])
        top_buffers = [line for _, line in sized[:5]]

    print(
        json.dumps(
            {
                "devices": n,
                "mesh": {"dp": dp, "fsdp": fsdp, "sp": sp, "tp": tp},
                "compile_s": round(compile_s, 1),
                "collectives": counts,
                "peak_bytes": sig.memory.get("temp_size_in_bytes"),
                "argument_bytes": sig.memory.get("argument_size_in_bytes"),
                "output_bytes": sig.memory.get("output_size_in_bytes"),
                "top_temp_buffers": top_buffers,
            }
        )
    )


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--out", type=str, default=None)
    p.add_argument("--child", type=int, default=None, help=argparse.SUPPRESS)
    p.add_argument("--mesh", type=str, default=None, help=argparse.SUPPRESS)
    args = p.parse_args()

    if args.child is not None:
        assert args.mesh, "--child requires --mesh dp,fsdp,sp,tp"
        dp, fsdp, sp, tp = (int(x) for x in args.mesh.split(","))
        _child(args.child, dp, fsdp, sp, tp)
        return

    import tempfile

    results = []
    for n, dp, fsdp, sp, tp in MESHES:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        dump_dir = tempfile.mkdtemp(prefix=f"scaling-dump-{n}-")
        env["_SCALING_REPORT_DUMP"] = dump_dir
        flags = [
            f
            for f in env.get("XLA_FLAGS", "").split()
            if "xla_force_host_platform_device_count" not in f and "xla_dump" not in f
        ]
        flags.append(f"--xla_force_host_platform_device_count={n}")
        flags.append(f"--xla_dump_to={dump_dir}")
        env["XLA_FLAGS"] = " ".join(flags)
        try:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--child", str(n),
                 "--mesh", f"{dp},{fsdp},{sp},{tp}"],
                env=env,
                capture_output=True,
                text=True,
                timeout=1800,
            )
        except subprocess.TimeoutExpired:
            # record the gap and keep going — partial artifacts must not look complete
            row = {"devices": n, "error": "compile exceeded 1800s"}
            print(json.dumps(row), flush=True)
            results.append(row)
            continue
        finally:
            shutil.rmtree(dump_dir, ignore_errors=True)
        line = proc.stdout.strip().splitlines()[-1] if proc.stdout.strip() else ""
        if proc.returncode != 0 or not line.startswith("{"):
            row = {"devices": n, "error": (proc.stderr or proc.stdout)[-500:]}
            print(json.dumps(row), flush=True)
            results.append(row)
            continue
        print(line, flush=True)
        results.append(json.loads(line))

    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)


if __name__ == "__main__":
    main()
