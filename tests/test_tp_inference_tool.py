"""tools/tensor_parallel_inference.py end-to-end on the virtual mesh (tp=2).

Parity: reference `tools/tensor_parallel_inference.py` (NCCL + _TP class + generate); here
the tool TP-shards a dolomite checkpoint from birth and generates. Previously untested."""

import json
import os
import subprocess
import sys

import jax
import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_tp_inference_tool_runs(tmp_path):
    # build a tiny checkpoint with a real (word-level) tokenizer
    from tokenizers import Tokenizer
    from tokenizers.models import WordLevel
    from tokenizers.pre_tokenizers import Whitespace

    vocab = {"<unk>": 0, "<eos>": 1}
    vocab.update({f"w{i}": i for i in range(2, 64)})
    tok = Tokenizer(WordLevel(vocab, unk_token="<unk>"))
    tok.pre_tokenizer = Whitespace()

    ckpt = tmp_path / "ckpt"
    ckpt.mkdir()
    tok.save(str(ckpt / "tokenizer.json"))
    json.dump(
        {"tokenizer_class": "PreTrainedTokenizerFast", "eos_token": "<eos>"},
        open(ckpt / "tokenizer_config.json", "w"),
    )

    from dolomite_engine_tpu.enums import Mode
    from dolomite_engine_tpu.model_wrapper.base import ModelWrapper

    wrapper = ModelWrapper(
        mode=Mode.training,
        pretrained_config=dict(
            model_type="gpt_dolomite", vocab_size=64, n_positions=64, n_embd=32,
            n_layer=2, n_head=4, attention_head_type="mha", position_embedding_type="rope",
            activation_function="swiglu", normalization_function="rmsnorm",
            bos_token_id=1, eos_token_id=1, pad_token_id=0,
        ),
        dtype="fp32",
    )
    from dolomite_engine_tpu.parallel.mesh import MeshManager

    MeshManager.destroy()
    MeshManager(devices=jax.devices()[:1])
    params = wrapper.init_params(jax.random.PRNGKey(0), MeshManager.get_mesh())
    MeshManager.destroy()
    wrapper.save_pretrained(str(ckpt), params=params)

    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "tensor_parallel_inference.py"),
         "--model", str(ckpt), "--tp", "2", "--prompt", "w2 w3 w4",
         "--max-new-tokens", "4"],
        capture_output=True,
        text=True,
        timeout=900,
        cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "XLA_FLAGS": "--xla_force_host_platform_device_count=2"},
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "[tp=2] generated" in proc.stdout
