"""Pallas TPU kernels for the paper's dynamic-model hot spots: block-sparse
attention, block-pruned matmul, grouped expert matmul and paged decode
attention.  Each package holds the kernel, its ``ops.py`` wrapper and a
pure-jnp ``ref.py`` oracle."""


def use_interpret() -> bool:
    """Whether model code runs the kernels in Pallas interpret mode.

    The one place that decides it: compiled Mosaic kernels on a TPU backend,
    the interpreter everywhere else (CPU tests).  ``chip_smoke.py`` refuses
    to run off the TPU and counts ``tpu_custom_call``s in the compiled step,
    so a chip run can never pass on the interpreter."""
    import jax
    return jax.default_backend() != "tpu"
