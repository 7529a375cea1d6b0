"""Pretraining wrapper: external loss over seq_len+1 token windows.

Parity: reference `dolomite_engine/model_wrapper/pretraining.py:16-236`
(`ModelWrapperForPretraining`): batch is `text` of length sequence_length+1, split into
input/label shifted views (`_prepare_inputs_ids_and_labels_for_forward`, lines 171-194); the
reference pre-registers `cu_seqlens`/`position_ids` buffers (196-236) or rebuilds them per batch
from EOS positions under `reset_attention_mask` (129-160). Here both are traced jnp ops inside
the jitted step (cummax-based segment derivation) — no buffers, no host sync. The reference's
TP broadcast of tokens from tp-rank0 (171-194) has no equivalent: data feed is per-host sharded
arrays and GSPMD replicates over tp implicitly. `loss_parallel` vocab-TP loss (89-127) is the
sharded softmax in `ops/loss.py`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..enums import Mode
from ..ops.loss import IGNORE_INDEX
from .base import ModelWrapper


def segment_ids_from_eos_jnp(tokens: jax.Array, eos_token_id: int) -> tuple[jax.Array, jax.Array]:
    """Traced version of `ops.packing.segment_ids_from_eos`: document segments increment after
    each EOS; positions reset at segment starts."""
    is_eos = tokens == eos_token_id
    shifted = jnp.concatenate([jnp.zeros_like(is_eos[:, :1]), is_eos[:, :-1]], axis=1)
    segment_ids = jnp.cumsum(shifted.astype(jnp.int32), axis=1) + 1

    idx = jnp.arange(tokens.shape[1], dtype=jnp.int32)[None, :]
    start_idx = jax.lax.cummax(jnp.where(shifted, idx, 0), axis=1)
    position_ids = idx - start_idx
    return segment_ids, position_ids


class ModelWrapperForPretraining(ModelWrapper):
    def __init__(
        self,
        *args,
        micro_batch_size: int | None = None,
        sequence_length: int | None = None,
        reset_attention_mask: bool = False,
        reset_position_ids: bool = False,
        **kwargs,
    ) -> None:
        self.micro_batch_size = micro_batch_size
        self.sequence_length = sequence_length
        self.reset_attention_mask = reset_attention_mask
        self.reset_position_ids = reset_position_ids
        super().__init__(*args, **kwargs)
        if self.is_encoder_decoder:
            raise ValueError(
                "pretraining consumes causal token streams; encoder-decoder families are "
                "trained through finetuning (tuning_method: full_finetuning), as in the "
                "reference (seq2seq enters via AutoModelForSeq2SeqLM finetuning only)"
            )

    def get_dummy_inputs(self) -> dict:
        seq = self.sequence_length or 8
        return {"input_ids": jnp.zeros((1, seq), jnp.int32)}

    def prepare_inputs_and_labels(self, text: jax.Array) -> dict:
        """text: [B, seq+1] int tokens -> model inputs + shifted labels (all traced)."""
        input_ids = text[:, :-1]
        labels = text[:, 1:]

        segment_ids = None
        position_ids = None
        if self.reset_attention_mask:
            segment_ids, reset_pos = segment_ids_from_eos_jnp(input_ids, self.config.eos_token_id)
            if self.reset_position_ids:
                position_ids = reset_pos
            # a label crossing a document boundary is invalid
            next_seg, _ = segment_ids_from_eos_jnp(text, self.config.eos_token_id)
            labels = jnp.where(next_seg[:, 1:] == segment_ids, labels, IGNORE_INDEX)
        elif self.reset_position_ids:
            _, position_ids = segment_ids_from_eos_jnp(input_ids, self.config.eos_token_id)

        return {
            "input_ids": input_ids,
            "labels": labels,
            "position_ids": position_ids,
            "segment_ids": segment_ids,
        }

    def loss(
        self,
        params,
        text: jax.Array,
        rngs: dict | None = None,
        train: bool = True,
        fp8_state=None,
    ):
        """Scalar LM loss (+ MoE aux loss folded in when the model emits one)."""
        batch = self.prepare_inputs_and_labels(text)
        with self.apply_scope():
            output = self.model.apply(
                self.variables(params, fp8_state),
                deterministic=not train,
                rngs=rngs,
                **batch,
            )
        # output.loss already includes the scaled router aux loss (models/gpt_dolomite.py
        # compute_aux_loss hook) — do not add it again
        if self.step_counter_names:
            return output.loss, output.counters
        return output.loss

    @property
    def step_counter_names(self) -> tuple:
        """Names of what the family's forward pass counts (models/nemotron_h.py); where
        there are any, :meth:`loss` returns ``(loss, counters)`` and the train step is
        built with ``has_aux``."""
        return tuple(getattr(self.model, "step_counter_names", ()))


class ModelWrapperForFinetuning(ModelWrapper):
    """Parity: reference `model_wrapper/finetuning.py:10-100`: forward = model's internal
    labels path; batches arrive padded with attention_mask + IGNORE_INDEX labels from
    `data/utils.py collate_fn`. The reference's TP broadcast of batches (lines 28-100) is
    unnecessary under SPMD data feed."""

    def loss(
        self,
        params,
        batch: dict,
        rngs: dict | None = None,
        train: bool = True,
        fp8_state=None,
    ):
        # seq2seq: input_ids/attention_mask feed the encoder; labels are decoder targets and
        # the model derives decoder_input_ids by shifting them right
        # (models/enc_dec_dolomite.py shift_right)
        inputs = {
            "input_ids": batch["input_ids"],
            "attention_mask": batch.get("attention_mask"),
            "labels": batch["labels"],
        }
        if not self.is_encoder_decoder:
            # padding-free packed batches carry these instead of attention_mask
            inputs["position_ids"] = batch.get("position_ids")
            inputs["segment_ids"] = batch.get("segment_ids")
        if self.neft_alpha is not None and train:
            # NEFTune (reference base.py:246-266): uniform noise scaled by alpha/sqrt(N*d)
            # added to input embeddings; implemented via the models' embedding_noise rng hook.
            rngs = dict(rngs or {})
            rngs.setdefault("neft", jax.random.PRNGKey(0))
        with self.apply_scope():
            output = self.model.apply(
                self.variables(params, fp8_state),
                deterministic=not train,
                rngs=rngs,
                **inputs,
            )
        # output.loss already includes the scaled router aux loss (models/gpt_dolomite.py
        # compute_aux_loss hook) — do not add it again
        return output.loss


def get_model(args, mode: Mode):
    """Factory (reference `model_wrapper/__init__.py:20-53`): TuningMethod -> wrapper class;
    pretraining gets micro_batch_size/sequence_length/reset_* kwargs."""
    from ..enums import TuningMethod

    tuning_method = args.tuning_args.tuning_method

    model_kwargs = {}
    if args.model_args.moe_implementation is not None:
        from ..enums import normalize_moe_implementation

        model_kwargs["moe_implementation"] = normalize_moe_implementation(
            args.model_args.moe_implementation
        )
    if args.model_args.scan_layers:
        model_kwargs["scan_layers"] = True

    common = dict(
        mode=mode,
        model_name=args.model_args.model_name,
        pretrained_config=args.model_args.pretrained_config,
        config_extras=args.model_args.config_extras,
        model_kwargs=model_kwargs or None,
        model_class=args.model_args.model_class,
        dtype=args.mixed_precision_args.dtype,
        efficient_initialization=args.model_args.efficient_initialization,
        attention_implementation=args.model_args.attention_implementation,
        use_padding_free_transformer=args.model_args.use_padding_free_transformer,
        tensor_parallel_word_embeddings=args.distributed_args.tensor_parallel_word_embeddings,
        sequence_parallel=args.distributed_args.sequence_parallel,
        zero_stage=args.distributed_args.stage,
        gradient_checkpointing_args=(
            args.distributed_args.gradient_checkpointing_args
            if args.distributed_args.gradient_checkpointing_method is not None
            else None
        ),
        tokenizer_name=args.tokenizer_args.tokenizer_name,
        additional_special_tokens=args.tokenizer_args.additional_special_tokens,
        trust_remote_code=args.model_args.trust_remote_code,
    )

    if tuning_method == TuningMethod.pretraining:
        block_size = None
        for ds in args.datasets:
            block_size = ds.class_args.get("sequence_length", block_size)
        return ModelWrapperForPretraining(
            **common,
            micro_batch_size=args.training_parameters.micro_batch_size,
            sequence_length=block_size,
            reset_attention_mask=args.model_args.reset_attention_mask,
            reset_position_ids=args.model_args.reset_position_ids,
        )
    elif tuning_method == TuningMethod.full_finetuning:
        return ModelWrapperForFinetuning(**common, neft_alpha=args.research_args.neft_alpha)
    elif tuning_method in (TuningMethod.prompt_tuning, TuningMethod.lora):
        from .peft import ModelWrapperForPEFT

        return ModelWrapperForPEFT(
            **common,
            neft_alpha=args.research_args.neft_alpha,
            tuning_args=args.tuning_args,
        )
    raise ValueError(f"unexpected tuning_method ({tuning_method})")
