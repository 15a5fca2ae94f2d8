from pytorch_distributed_training_tpu.utils.lazy import lazy_exports

# resolved on first use: ``train.manifest`` is jax-free and imported by the
# fleet coordinator and scripts/verify_checkpoint.py (utils/lazy.py)
_LAZY = {
    "adamw_with_schedule": "optim",
    "linear_warmup_schedule": "optim",
    "TrainState": "state",
    "create_train_state": "state",
    "make_train_step": "step",
    "make_eval_step": "step",
    "calibrate_quant": "step",
    "MetricAccumulator": "metrics",
}

__all__ = sorted(_LAZY)
__getattr__ = lazy_exports(__name__, _LAZY)
