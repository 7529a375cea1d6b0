"""Unshard entry point: training checkpoint -> HF-format safetensors dir.

Parity: reference `dolomite_engine/unshard.py:7-21`: `load_checkpoint_for_inference(use_meta=True)`
then rank-0 `model.save_pretrained(unsharded_path, state_dict)`. Under GSPMD "unsharding" is just
restoring with replicated shardings (checkpointing.load_checkpoint_for_inference); the reference's
per-backend merge paths and TP fused-weight fixups (checkpointing.py:326-362) don't exist here.
"""

from __future__ import annotations

import jax

from .arguments import UnshardingArgs, get_args
from .checkpointing import load_checkpoint_for_inference
from .enums import Mode
from .utils import init_distributed


def main(args: UnshardingArgs | None = None) -> None:
    if args is None:
        args = get_args(Mode.unsharding)

    init_distributed()

    model, params, _ = load_checkpoint_for_inference(args, Mode.unsharding)

    if jax.process_index() == 0:
        model.save_pretrained(args.unsharded_path, params=params)


if __name__ == "__main__":
    main()
