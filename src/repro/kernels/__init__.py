"""Pallas kernels of the stratification hot path (the fused ``sim_sweep``
pass, and ``sim_topk`` for the raised-k collection retry) and the model
kernels."""


def interpret_mode() -> bool:
    """Whether Pallas kernels run under the interpreter: compiled on a TPU,
    interpreted on every other backend.  The ops decide it here and nowhere
    else, so no caller on a TPU can reach interpret mode by omission."""
    import jax

    return jax.default_backend() != "tpu"
