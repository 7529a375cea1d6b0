#!/bin/bash
# End-to-end lifecycle on a laptop-class CPU in a few minutes: build a word-level
# tokenizer, tokenize a tiny corpus into the Megatron mmap format, pretrain a toy
# GPTDolomite on a virtual 8-device mesh (ZeRO-3 + packed segment ids), resume from the
# checkpoint, batch-generate, and export HF-layout weights. Every stage is the same code
# path a pod run uses — only the mesh and model are tiny.
#
# Usage: bash examples/quickstart.sh [workdir]   (default: /tmp/dolomite-quickstart)
set -euo pipefail
cd "$(dirname "$0")/.."
WORK="${1:-/tmp/dolomite-quickstart}"
export JAX_PLATFORMS=cpu
export XLA_FLAGS="--xla_force_host_platform_device_count=8"
# only wipe a directory this script created (marker file), never arbitrary user data
if [ -e "$WORK" ] && [ ! -f "$WORK/.dolomite-quickstart" ]; then
  echo "refusing to delete pre-existing '$WORK' (no .dolomite-quickstart marker); pass a fresh path" >&2
  exit 1
fi
rm -rf "$WORK" && mkdir -p "$WORK" && touch "$WORK/.dolomite-quickstart"

echo "=== 1/6 tokenizer + raw corpus"
python - "$WORK" <<'EOF'
import json, random, sys
from tokenizers import Tokenizer
from tokenizers.models import WordLevel
from tokenizers.pre_tokenizers import Whitespace

work = sys.argv[1]
words = [f"w{i}" for i in range(500)]
vocab = {"<bos>": 0, "<eos>": 1, "<pad>": 2, **{w: i + 3 for i, w in enumerate(words)}}
tok = Tokenizer(WordLevel(vocab, unk_token="<eos>"))
tok.pre_tokenizer = Whitespace()
tok.save(work + "/tokenizer.json")
json.dump(
    {"tokenizer_class": "PreTrainedTokenizerFast", "bos_token": "<bos>",
     "eos_token": "<eos>", "pad_token": "<pad>"},
    open(work + "/tokenizer_config.json", "w"),
)
rng = random.Random(0)
with open(work + "/corpus.jsonl", "w") as f:
    for _ in range(400):
        f.write(json.dumps({"text": " ".join(rng.choices(words, k=rng.randint(12, 90)))}) + "\n")
print("wrote", work + "/corpus.jsonl")
EOF

echo "=== 2/6 tokenize into mmap bin/idx"
python tools/megatron_dataset/preprocess_data.py \
  --input "$WORK/corpus.jsonl" --tokenizer "$WORK" \
  --output-prefix "$WORK/corpus" --append-eod --workers 2 --chunk-size 16

echo "=== 3/6 pretrain 6 steps (ZeRO-3, packed segment ids, virtual 8-device mesh)"
python - "$WORK" <<'EOF' > "$WORK/pretrain.yml"
import sys
print(f"""
datasets:
  - class_name: MegatronDataset
    data_name: Megatron
    data_sampling_ratio: 1
    class_args:
      eval_steps: 0
      data_cache_path: {sys.argv[1]}/cache
      data_path: [{sys.argv[1]}/corpus_text]
      split: 100,0,0
      sequence_length: 64
tokenizer_args:
  tokenizer_name: {sys.argv[1]}
model_args:
  model_class: AutoModelForCausalLM
  reset_attention_mask: true
  reset_position_ids: true
  pretrained_config:
    model_type: gpt_dolomite
    vocab_size: 512
    n_positions: 64
    n_embd: 64
    n_layer: 2
    n_head: 4
    attention_head_type: mha
    position_embedding_type: rope
    activation_function: swiglu
    normalization_function: rmsnorm
    add_bias: false
    resid_pdrop: 0.0
    embd_pdrop: 0.0
    attn_pdrop: 0.0
    bos_token_id: 0
    eos_token_id: 1
    pad_token_id: 2
tuning_args: {{tuning_method: pretraining}}
distributed_args: {{stage: 3}}
training_parameters:
  num_training_steps: 6
  micro_batch_size: 2
  gradient_accumulation_steps: 1
  eval_during_training: false
save_args:
  save_path: {sys.argv[1]}/ckpt
  save_interval: 3
  async_checkpointing: true
logging_args: {{log_interval: 1}}
random_args: {{seed: 7}}
""")
EOF
python -m dolomite_engine_tpu.pretrain --config "$WORK/pretrain.yml"

echo "=== 4/6 resume for 3 more steps"
python - "$WORK" <<'EOF'
import sys
p = sys.argv[1] + "/pretrain.yml"
s = open(p).read().replace("num_training_steps: 6", "num_training_steps: 9")
s += f"\nload_args:\n  load_path: {sys.argv[1]}/ckpt\n"
open(p, "w").write(s)
EOF
python -m dolomite_engine_tpu.pretrain --config "$WORK/pretrain.yml"

echo "=== 5/6 batch generation from the checkpoint"
python - "$WORK" <<'EOF' > "$WORK/generate.yml"
import sys
print(f"""
load_args:
  load_path: {sys.argv[1]}/ckpt
datasets:
  - class_name: DebugDataset
    data_name: debug
    data_sampling_ratio: 1
    max_input_tokens: 16
    max_output_tokens: 16
    class_args: {{num_examples: 8}}
generation_parameters:
  batch_size: 4
  max_new_tokens: 8
  do_sample: false
output_dir: {sys.argv[1]}/generations
mixed_precision_args: {{dtype: fp32}}
""")
EOF
python -m dolomite_engine_tpu.generate --config "$WORK/generate.yml"
head -c 300 "$WORK"/generations/*.jsonl && echo

echo "=== 6/6 unshard to HF-layout safetensors"
python - "$WORK" <<'EOF' > "$WORK/unshard.yml"
import sys
print(f"""
load_args:
  load_path: {sys.argv[1]}/ckpt
unsharded_path: {sys.argv[1]}/hf-export
mixed_precision_args: {{dtype: fp32}}
""")
EOF
python -m dolomite_engine_tpu.unshard --config "$WORK/unshard.yml"
ls "$WORK/hf-export"

echo "=== quickstart OK: $WORK"
