"""Optimizer factory over optax, with µP param groups.

Parity: reference `dolomite_engine/optimization/optimizer.py` registers 23 named classes
(4 Apex fused, 7 DeepSpeed, 12 torch; lines 56-84). On TPU the fused/CPU/1-bit kernel variants
are meaningless — XLA fuses optax updates — so every Adam-family alias maps to one optax
implementation; the registry keeps ALL reference names so YAML configs run unchanged.
Defaults (reference `arguments.py:237-246`): TorchAdamW, lr 1e-5, wd 0.1, betas (0.9, 0.95),
eps 1e-10.

µP groups (reference `optimizer.py:85-126`): attention/MLP non-bias params train at
`lr / m_width`; implemented as an optax.multi_transform over a label tree derived from param
paths (`.../attn/...` or `.../mlp/...` kernels -> "mup").
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import optax

from ..enums import ParamsGroupMethod
from .scheduler import get_scheduler

# every reference optimizer name -> optax factory(lr_schedule, args)
# kernel-variant aliases collapse to their mathematical equivalent


def _resolve_mu_dtype(value):
    if value is None or not isinstance(value, str):
        return value
    from ..utils.mixed_precision import string_to_dtype

    try:
        return string_to_dtype(value)
    except Exception:
        return value  # numpy-style names ("bfloat16") pass through to optax


def _adamw(lr, args):
    return optax.adamw(
        lr,
        b1=args.get("betas", (0.9, 0.95))[0],
        b2=args.get("betas", (0.9, 0.95))[1],
        eps=args.get("eps", 1e-10),
        weight_decay=args.get("weight_decay", 0.1),
        # TPU-only knob: keep the first moment in bf16 (HBM saver; torch AdamW has no
        # equivalent — fused torch optimizers always store fp32 states). Accepts the repo's
        # dtype names ("bf16") as well as numpy-style ones.
        mu_dtype=_resolve_mu_dtype(args.get("mu_dtype")),
    )


def _adam(lr, args):
    return optax.adam(
        lr,
        b1=args.get("betas", (0.9, 0.95))[0],
        b2=args.get("betas", (0.9, 0.95))[1],
        eps=args.get("eps", 1e-10),
    )


def _sgd(lr, args):
    return optax.sgd(lr, momentum=args.get("momentum", 0.0), nesterov=args.get("nesterov", False))


def _lamb(lr, args):
    return optax.lamb(
        lr,
        b1=args.get("betas", (0.9, 0.95))[0],
        b2=args.get("betas", (0.9, 0.95))[1],
        eps=args.get("eps", 1e-10),
        weight_decay=args.get("weight_decay", 0.1),
    )


def _adagrad(lr, args):
    return optax.adagrad(lr, eps=args.get("eps", 1e-10))


def _adadelta(lr, args):
    return optax.adadelta(lr, rho=args.get("rho", 0.9), eps=args.get("eps", 1e-6))


def _adamax(lr, args):
    return optax.adamax(
        lr,
        b1=args.get("betas", (0.9, 0.95))[0],
        b2=args.get("betas", (0.9, 0.95))[1],
        eps=args.get("eps", 1e-10),
    )


def _nadam(lr, args):
    return optax.nadam(
        lr,
        b1=args.get("betas", (0.9, 0.95))[0],
        b2=args.get("betas", (0.9, 0.95))[1],
        eps=args.get("eps", 1e-10),
    )


def _radam(lr, args):
    return optax.radam(
        lr,
        b1=args.get("betas", (0.9, 0.95))[0],
        b2=args.get("betas", (0.9, 0.95))[1],
        eps=args.get("eps", 1e-10),
    )


def _rmsprop(lr, args):
    return optax.rmsprop(
        lr, decay=args.get("alpha", 0.99), eps=args.get("eps", 1e-8), momentum=args.get("momentum", 0.0)
    )


def _rprop(lr, args):
    return optax.rprop(lr)


def _novograd(lr, args):
    return optax.novograd(
        lr,
        b1=args.get("betas", (0.9, 0.95))[0],
        b2=args.get("betas", (0.9, 0.95))[1],
        eps=args.get("eps", 1e-10),
        weight_decay=args.get("weight_decay", 0.0),
    )


_OPTIMIZER_FACTORIES: dict[str, Callable] = {
    "ApexFusedAdam": _adamw,
    "ApexFusedLAMB": _lamb,
    "ApexFusedNovoGrad": _novograd,
    "ApexFusedSGD": _sgd,
    "DeepSpeedCPUAdagrad": _adagrad,
    "DeepSpeedCPUAdam": _adamw,
    "DeepSpeedFusedAdam": _adamw,
    "DeepSpeedFusedLAMB": _lamb,
    "DeepSpeedOnebitAdam": _adamw,
    "DeepSpeedOnebitLAMB": _lamb,
    "DeepSpeedZeroOneAdam": _adamw,
    "TorchAdadelta": _adadelta,
    "TorchAdagrad": _adagrad,
    "TorchAdam": _adam,
    "TorchAdamax": _adamax,
    "TorchAdamW": _adamw,
    "TorchASGD": _sgd,
    "TorchLBFGS": None,  # no batch second-order optimizer on TPU
    "TorchNAdam": _nadam,
    "TorchRAdam": _radam,
    "TorchRMSprop": _rmsprop,
    "TorchRprop": _rprop,
    "TorchSGD": _sgd,
}


def get_mup_label_tree(params: Any) -> Any:
    """Label each param "mup" (attention/MLP non-bias weights) or "normal".

    Reference `optimizer.py:100-115`: modules of type Attention/MLP contribute their non-bias
    params to the mup group. Our param tree paths look like
    `transformer/h_3/attn/c_attn/kernel`; experts in MoE blocks live under `mlp`/`moe` too.
    """

    def label(path, leaf) -> str:
        keys = [getattr(p, "key", str(p)) for p in path]
        in_mup_module = any(k in ("attn", "mlp", "moe") for k in keys)
        is_bias = keys[-1] == "bias"
        return "mup" if in_mup_module and not is_bias else "normal"

    return jax.tree_util.tree_map_with_path(label, params)


def _hold_leaves(names: tuple[str, ...]) -> optax.GradientTransformation:
    """Zero the update of every leaf whose last path key is in `names`."""

    def update(updates, state, params=None):
        del params

        def hold(path, u):
            return jax.numpy.zeros_like(u) if getattr(path[-1], "key", None) in names else u

        return jax.tree_util.tree_map_with_path(hold, updates), state

    return optax.GradientTransformation(lambda params: optax.EmptyState(), update)


def get_optimizer(
    optimizer_class_name: str,
    optimizer_class_args: dict,
    lr_schedule: Callable,
    params_group_method: ParamsGroupMethod | None = None,
    model_config=None,
    params=None,
) -> optax.GradientTransformation:
    """Build the optax chain. `lr_schedule` maps step -> absolute lr."""
    if optimizer_class_name not in _OPTIMIZER_FACTORIES:
        raise ValueError(f"invalid optimizer class '{optimizer_class_name}'")
    factory = _OPTIMIZER_FACTORIES[optimizer_class_name]
    if factory is None:
        raise ValueError(f"optimizer '{optimizer_class_name}' is not supported on TPU")

    buffers = tuple(getattr(model_config, "buffer_names", ()))
    if buffers:
        # leaves the public model keeps as buffers (nemotron_h's router correction bias):
        # no gradient reaches them, and weight decay must not move them either
        inner = factory

        def factory(schedule, args):
            return optax.chain(inner(schedule, args), _hold_leaves(buffers))

    if params_group_method is None:
        return factory(lr_schedule, optimizer_class_args)

    if params_group_method == ParamsGroupMethod.mup:
        assert model_config is not None and params is not None
        assert model_config.init_method == "mup", (
            "both init method for model and params group method for optimizer should be set to mup"
        )
        m_width = model_config.m_width

        def mup_schedule(step):
            return lr_schedule(step) / m_width

        labels = get_mup_label_tree(params)
        return optax.multi_transform(
            {
                "normal": factory(lr_schedule, optimizer_class_args),
                "mup": factory(mup_schedule, optimizer_class_args),
            },
            labels,
        )

    raise ValueError(f"unexpected params_group_method ({params_group_method})")


def build_optimizer_from_args(args, model) -> tuple[optax.GradientTransformation, Callable]:
    """(optimizer, lr schedule) as a `TrainingArgs` describes them, for `model`'s parameters."""
    lr_scheduler_args = args.lr_scheduler_args
    lr_schedule = get_scheduler(
        num_warmup_steps=lr_scheduler_args.num_warmup_steps,
        num_constant_steps=lr_scheduler_args.num_constant_steps,
        num_decay_steps=lr_scheduler_args.num_decay_steps,
        num_training_steps=args.training_parameters.num_training_steps,
        lr_decay_style=lr_scheduler_args.lr_decay_style,
        lr_decay_factor=lr_scheduler_args.lr_decay_factor,
        extra_lr_scheduler_args=lr_scheduler_args.extra_lr_scheduler_args,
        base_lr=args.optimizer_args.class_args.get("lr", 1e-5),
    )
    optimizer = get_optimizer(
        optimizer_class_name=args.optimizer_args.class_name,
        optimizer_class_args=args.optimizer_args.class_args,
        lr_schedule=lr_schedule,
        params_group_method=args.optimizer_args.params_group_method,
        model_config=model.config,
        params=model.abstract_params(),
    )
    return optimizer, lr_schedule
