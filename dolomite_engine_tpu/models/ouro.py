"""`ouro`: Ouro-2.6B's family (a looped language model), training path.

ONE stack of `n_layer` blocks, applied `total_ut_steps` times over the same weights:

  block l   a = h + N2_l(Attn_l(N1_l(h)));  h' = a + N4_l(MLP_l(N3_l(a)))      four RMSNorms: each
            sub-layer's input AND its output is normed (so the residual add cannot be fused
            with the norm that follows it, as `modeling_utils.Block` fuses `ln_2`'s);
            Attn is the repo's `Attention` (causal multi-head, rope by halves), MLP its SwiGLU `MLP`
  pass t    h_t = N_f(block_L(... block_1(h_{t-1}))),  h_0 = E[x]: the final norm closes every
            pass, and its output is what the next pass reads and what the head reads
  gate      lambda_t = sigmoid(w_g . h_t + b_g) a token;  p_t = lambda_t prod_{j<t} (1 - lambda_j)
            for t < T and p_T = prod_{j<T} (1 - lambda_j): the last pass takes what is left
  loss      mean over target tokens of  sum_t p_t CE(W_head h_t, y) - beta H(p)   (+ the
            trainer's z-loss on each pass's logits, weighed by p_t too)

The passes are a `lax.scan` (`nn.scan` with the parameters broadcast) whose body is the whole
stack, every block under `jax.checkpoint`: the program holds one copy of each block's forward
and backward, a weight's gradient is summed over the passes by the scan's transpose, and what is
kept between forward and backward is the input of each of the ``T x L`` block applications. The
head reads each row's ``T`` passes laid end to end, once (`ops/loss.fused_linear_token_cross_entropy`: the
table is read a tile once for all passes, its gradient accumulated once), and hands back every
token's cross-entropy of every pass; the gate's weights meet them outside, under
``head_loss/pass_weighting``, so the gate learns through ordinary autodiff of that product.

Packed rows (``segment_ids``): attention and positions reset at document boundaries. Training
path only; what is not built raises from `refuse` below.

Scopes inside the jitted step (docs/OBSERVABILITY.md "Phases of the train step"): ``blocks/pass``
(the scan's one body: the passes are its iterations in a device trace, not four names), with
``block_norms`` on the four norms of a block; ``exit_gate``; ``head_loss`` with
``pass_weighting`` inside it.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..enums import AttentionImplementation
from ..ops.attention import watch_kernel_residuals
from ..ops.loss import IGNORE_INDEX, derive_causal_labels, fused_linear_token_cross_entropy
from ..ops.rope import RoPEParams, get_cos_sin
from ..parallel.sharding import logical_constraint
from .config import OuroConfig
from .gpt_dolomite import CausalLMOutput, HeadTableForCausalLM, resolve_remat_policy, say_remat_plan
from .modeling_utils import MLP, Attention, ParameterizedEmbedding, get_norm, sandwich_normed_block

NOT_BUILT = {
    "kv_cache": "a KV cache (generation and the serving engine need one per pass and layer)",
    "tp": "a mesh with tp > 1 (the gate and the passes' stacked head rows were never run sharded over heads)",
    "scan_layers": "scan_layers (the passes are the scan; a scan over blocks inside it is not built: run scan_layers: false)",
}


def refuse(what: str) -> None:
    """The one place the family says what it cannot do yet (`NOT_BUILT`; ROADMAP Queue 2)."""
    raise NotImplementedError(f"ouro: {NOT_BUILT[what]} is not built; the training path on dp / fsdp meshes only")


def rematerialized_blocks(checkpoint_every: int, n_layer: int) -> tuple:
    """Which blocks of the stack sit under `jax.checkpoint` (every `checkpoint_every`-th; 0: none)."""
    return tuple(checkpoint_every > 0 and i % checkpoint_every == 0 for i in range(n_layer))


def pass_step_counter_names(passes: int) -> tuple:
    """Names of what a step counts beside the loss: every pass's mean cross-entropy and mean exit
    probability, the mean entropy of the gate's distribution, the weighted cross-entropy (the
    loss without its entropy term and z-loss) and the last pass's cross-entropy again (what
    generation at `early_exit_threshold` 1 would score)."""
    return (
        tuple(f"pass_loss_{t + 1}" for t in range(passes))
        + tuple(f"exit_mass_{t + 1}" for t in range(passes))
        + ("exit_entropy", "weighted_loss", "last_pass_loss")
    )


class OuroBlock(nn.Module):
    """A sandwich-normed block: each sub-layer between a norm of its input and one of its output."""

    config: OuroConfig
    attention_implementation: AttentionImplementation = AttentionImplementation.sdpa
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, hidden_states: jax.Array, attention_mask=None, segment_ids=None, rope_cos_sin=None, deterministic: bool = True) -> jax.Array:
        config = self.config
        attn = Attention(config=config, attention_implementation=self.attention_implementation, dtype=self.dtype, name="attn")
        hidden_states, _ = sandwich_normed_block(
            config,
            self.dtype,
            hidden_states,
            lambda h: attn(h, attention_mask=attention_mask, segment_ids=segment_ids, rope_cos_sin=rope_cos_sin, deterministic=deterministic)[0],
            lambda h: MLP(config=config, dtype=self.dtype, name="mlp")(h, deterministic=deterministic),
        )
        return hidden_states


class OuroStack(nn.Module):
    """One pass: the blocks, then the final norm. The body of the scan over passes, so its
    signature is a scan body's: ``(carry, broadcast arguments) -> (carry, this pass's output)``."""

    config: OuroConfig
    attention_implementation: AttentionImplementation = AttentionImplementation.sdpa
    dtype: Any = jnp.float32
    checkpoint_every: int = 0
    checkpoint_policy: str | None = None

    def setup(self) -> None:
        config = self.config
        # the scan over passes applies every block `total_ut_steps` times: whatever a block kept
        # would be kept that often (2.03 GiB at the cell's size, of 0.45 free), so `full` keeps nothing
        remat_policy = resolve_remat_policy(self.checkpoint_policy, applications_per_block=config.total_ut_steps)
        self.rematerialized = rematerialized_blocks(self.checkpoint_every, config.n_layer)
        blocks = []
        for rematerialized in self.rematerialized:
            cls = OuroBlock
            if rematerialized:
                # flax counts the module instance as argument 0; deterministic is arg 5.
                # prevent_cse stays ON although this is a scan's body: the body holds `n_layer`
                # checkpoints, not one, and without the barriers XLA is free to run all their
                # replays before the first block's backward (the compiler's report at the cell's
                # size, PR 38: four blocks' replays live at once, 16.7 GB asked of 15.75)
                cls = nn.remat(cls, static_argnums=(5,), policy=remat_policy)
            blocks.append(cls(config=config, attention_implementation=self.attention_implementation, dtype=self.dtype))
        self.h = blocks
        self.ln_f = get_norm(config, self.dtype)

    def __call__(self, hidden_states: jax.Array, attention_mask, segment_ids, rope_cos_sin, deterministic: bool):
        with jax.named_scope("pass"):
            for block in self.h:
                hidden_states = block(hidden_states, attention_mask, segment_ids, rope_cos_sin, deterministic)
            with jax.named_scope("block_norms"):
                hidden_states = self.ln_f(hidden_states)
        return hidden_states, hidden_states


def loop_plan(config: OuroConfig, rematerialized: tuple, batch: int, seq: int, itemsize: int) -> dict:
    """What the telemetry event ``loop_plan`` says, once a traced model: the loop's passes and
    blocks, the block applications of a step, and the bytes of the block inputs kept between
    forward and backward (one ``[batch, seq, n_embd]`` a rematerialized application, plus every
    pass's output for the head)."""
    passes, blocks = config.total_ut_steps, config.n_layer
    row = batch * seq * config.n_embd * itemsize
    return {
        "passes": passes,
        "blocks": blocks,
        "block_applications": passes * blocks,
        "applications_rematerialized": passes * sum(rematerialized),
        "head_readings": passes,
        "kept_input_bytes": passes * (sum(rematerialized) + 1) * row,
        "rows": batch,
        "tokens_per_row": seq,
    }


class OuroModel(nn.Module):
    config: OuroConfig
    attention_implementation: AttentionImplementation = AttentionImplementation.sdpa
    dtype: Any = jnp.float32
    checkpoint_every: int = 0
    checkpoint_policy: str | None = None
    scan_layers: bool = False

    def setup(self) -> None:
        config = self.config
        if self.scan_layers:
            refuse("scan_layers")
        from ..parallel.mesh import MeshManager

        if MeshManager.is_initialized() and MeshManager.axis_size("tp") > 1:
            refuse("tp")
        self.wte = ParameterizedEmbedding(
            num_embeddings=config.vocab_size, features=config.n_embd, std=config.initializer_range, dtype=self.dtype
        )
        self.rope_params = RoPEParams.from_config(config.head_dim, config.rope_theta, config.rope_scaling, config.n_positions)
        self.rematerialized = rematerialized_blocks(self.checkpoint_every, config.n_layer)
        # the loop: ONE stack, its parameters broadcast to every iteration (the scan's transpose
        # sums their gradients over the passes), the hidden states carried
        self.stack = nn.scan(
            OuroStack,
            variable_broadcast="params",
            split_rngs={"params": False, "dropout": True},
            in_axes=(nn.broadcast,) * 4,
            out_axes=1,  # [batch, passes, seq, n_embd]: the rows stay the leading axis, as every sharding has them
            length=config.total_ut_steps,
        )(
            config=config,
            attention_implementation=self.attention_implementation,
            dtype=self.dtype,
            checkpoint_every=self.checkpoint_every,
            checkpoint_policy=self.checkpoint_policy,
        )

    def __call__(
        self,
        input_ids: jax.Array,
        position_ids: jax.Array | None = None,
        attention_mask: jax.Array | None = None,
        segment_ids: jax.Array | None = None,
        kv_caches: list | None = None,
        cache_index: jax.Array | None = None,
        deterministic: bool = True,
        inputs_embeds: jax.Array | None = None,
    ) -> jax.Array:
        """Every pass's normed hidden states, ``[batch, total_ut_steps, seq, n_embd]``."""
        if kv_caches is not None:
            refuse("kv_cache")
        batch, seq = input_ids.shape
        with jax.named_scope("embed"):
            hidden_states = self.wte(input_ids) if inputs_embeds is None else inputs_embeds
            hidden_states = logical_constraint(hidden_states, ("act_batch", "act_seq", "act_embed"))
            if position_ids is None:
                position_ids = jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32)[None], (batch, seq))
            rope_cos_sin = get_cos_sin(self.rope_params, position_ids, dtype=self.dtype)
        if segment_ids is None and attention_mask is not None:
            segment_ids = attention_mask.astype(jnp.int32)  # the pad tokens are a document of their own
        with jax.named_scope("blocks"), watch_kernel_residuals() as seen:
            _, passes = self.stack(hidden_states, attention_mask, segment_ids, rope_cos_sin, deterministic)
        # one body for every pass: what its trace tagged, a block's every application tagged
        per_block = (list(seen) + [0] * self.config.n_layer)[: self.config.n_layer]
        say_remat_plan(self, per_block, applications_per_block=self.config.total_ut_steps)
        if self.checkpoint_every:
            from ..utils.telemetry import get_telemetry

            get_telemetry().event_once(
                "loop_plan", **loop_plan(self.config, self.rematerialized, batch, seq, jnp.dtype(self.dtype).itemsize)
            )
        return passes


class ExitGate(nn.Module):
    """``w_g . h + b_g`` a token, in float32: the logit of stopping after this pass."""

    std: float = 0.02

    @nn.compact
    def __call__(self, hidden_states: jax.Array) -> jax.Array:
        kernel = self.param(
            "kernel", nn.with_logical_partitioning(nn.initializers.normal(self.std), ("embed", None)), (hidden_states.shape[-1], 1), jnp.float32
        )
        bias = self.param("bias", nn.with_logical_partitioning(nn.initializers.zeros_init(), (None,)), (1,), jnp.float32)
        return jnp.einsum("...h,h->...", hidden_states.astype(jnp.float32), kernel[:, 0]) + bias[0]


def exit_distribution(gate_logits: jax.Array) -> tuple[jax.Array, jax.Array]:
    """``(log p, p)`` over the passes (axis 1 of ``[batch, passes, seq]``) from the gate's
    logits: pass t stops with probability lambda_t if none before it did, and the last pass
    takes what is left. In logarithms (`log_sigmoid` of the logit and of its negative), so a
    gate driven far to one side gives no 0 x inf."""
    log_stop, log_go = jax.nn.log_sigmoid(gate_logits), jax.nn.log_sigmoid(-gate_logits)
    went_on = jnp.cumsum(log_go, axis=1) - log_go  # sum over j < t of log(1 - lambda_j)
    log_p = jnp.concatenate([(log_stop + went_on)[:, :-1], went_on[:, -1:]], axis=1)
    return log_p, jnp.exp(log_p)


class OuroForCausalLM(HeadTableForCausalLM):
    """The loop under the repo's untied head table, an exit gate, and the loss that weighs the
    passes' cross-entropies by the gate's distribution."""

    base_model_cls: type = OuroModel

    def setup(self) -> None:
        super().setup()
        self.exit_gate = ExitGate(std=self.config.initializer_range)

    @property
    def family_counter_names(self) -> tuple:
        return pass_step_counter_names(self.config.total_ut_steps)

    @nn.nowrap  # (as `fused_head_loss`: the scopes are the caller's)
    def token_losses(self, passes: jax.Array, labels: jax.Array) -> tuple[jax.Array, jax.Array]:
        """``(cross-entropy, log-sum-exp)`` of every token of every pass, ``[B, T, S]`` float32
        each: the head read once over each row's passes laid end to end (the chunked loss cuts
        along the sequence, so a row's sharding is untouched)."""
        config = self.config
        batch, steps, seq, hidden = passes.shape
        stacked, stacked_labels = passes.reshape(batch, steps * seq, hidden), jnp.tile(labels, (1, steps))
        if config.fused_lm_head_loss:
            head_in, table = self._lm_head_operands(stacked)
            loss, lse = fused_linear_token_cross_entropy(
                head_in, table, stacked_labels, chunk_size=config.loss_chunk_size,
                upcast=config.upcast_logits_for_loss, compute_dtype=self.dtype,
            )
        else:
            logits = self.compute_logits(stacked)
            logits = logits.astype(jnp.float32) if config.upcast_logits_for_loss else logits
            lse = jax.scipy.special.logsumexp(logits, axis=-1).astype(jnp.float32)
            picked = jnp.take_along_axis(logits, jnp.maximum(stacked_labels, 0)[..., None], axis=-1)[..., 0]
            loss = jnp.where(stacked_labels != IGNORE_INDEX, lse - picked.astype(jnp.float32), 0.0)
        return loss.reshape(batch, steps, seq), lse.reshape(batch, steps, seq)

    def __call__(
        self,
        input_ids: jax.Array,
        position_ids: jax.Array | None = None,
        attention_mask: jax.Array | None = None,
        segment_ids: jax.Array | None = None,
        labels: jax.Array | None = None,
        kv_caches: list | None = None,
        cache_index: jax.Array | None = None,
        deterministic: bool = True,
        compute_loss: bool = False,
        inputs_embeds: jax.Array | None = None,
    ) -> CausalLMOutput:
        config = self.config
        passes = self.transformer(
            input_ids,
            position_ids=position_ids,
            attention_mask=attention_mask,
            segment_ids=segment_ids,
            kv_caches=kv_caches,
            cache_index=cache_index,
            deterministic=deterministic,
            inputs_embeds=inputs_embeds,
        )
        if not (compute_loss or labels is not None):
            if self.is_initializing():
                self.exit_gate(passes)  # (the gate's parameters exist from any call)
            with jax.named_scope("head_loss"):
                # at `early_exit_threshold` 1 every token runs every pass: the last pass's logits
                return CausalLMOutput(logits=self.compute_logits(passes[:, -1]))
        if labels is None:
            with jax.named_scope("head_loss"):
                labels = derive_causal_labels(input_ids, attention_mask, segment_ids)
        loss, counters = self.gated_loss(passes, labels)
        counters.update(self.splash_step_counters(passes[:, 0], segment_ids, attention_mask))
        return CausalLMOutput(loss=loss, counters=counters)

    def gated_loss(self, passes: jax.Array, labels: jax.Array) -> tuple[jax.Array, dict]:
        """``(loss, counters)`` from every pass's normed hidden states ``[B, T, S, H]``: the gate's
        distribution over the passes a token, the passes' cross-entropies weighed by it, less
        `exit_entropy_coef` times its entropy (`pass_step_counter_names` names the counters)."""
        config = self.config
        per_pass = lambda x: logical_constraint(x, ("act_batch", None, "act_seq"))  # noqa: E731  ([B, T, S])
        with jax.named_scope("exit_gate"):
            gate_logits = per_pass(self.exit_gate(passes))
        with jax.named_scope("head_loss"):
            token_loss, lse = (per_pass(x) for x in self.token_losses(passes, labels))
            with jax.named_scope("pass_weighting"):
                log_p, p = exit_distribution(gate_logits)
                valid = (labels != IGNORE_INDEX).astype(jnp.float32)[:, None]
                count = jnp.maximum(jnp.sum(valid), 1.0)
                mean = lambda x: jnp.sum(x * valid, axis=(0, 2)) / count  # noqa: E731  ([B, 1 or T, S] -> [1 or T]: over target tokens)
                weighted_loss = mean(jnp.sum(p * token_loss, axis=1, keepdims=True))[0]
                entropy = mean(-jnp.sum(p * log_p, axis=1, keepdims=True))[0]
                loss = weighted_loss - config.exit_entropy_coef * entropy
                if config.z_loss_coef != 0.0:
                    loss = loss + config.z_loss_coef * mean(jnp.sum(p * jnp.square(lse), axis=1, keepdims=True))[0]
                pass_losses, exit_mass = mean(token_loss), mean(p)
        counters = {f"pass_loss_{t + 1}": pass_losses[t] for t in range(config.total_ut_steps)}
        counters.update({f"exit_mass_{t + 1}": exit_mass[t] for t in range(config.total_ut_steps)})
        counters.update(exit_entropy=entropy, weighted_loss=weighted_loss, last_pass_loss=pass_losses[-1])
        return loss, jax.lax.stop_gradient(counters)

    def init_kv_caches(self, batch_size: int, max_length: int, dtype=None) -> list:
        refuse("kv_cache")
