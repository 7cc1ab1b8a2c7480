"""Pallas TPU kernels for the compute hot-spots Xenos optimizes.

Each kernel directory holds:
  * ``<name>.py`` — the pl.pallas_call with explicit BlockSpec VMEM tiling,
  * ``ops.py``    — the jit'd public wrapper (interpreted on the CPU
    backend, compiled on a TPU, refused elsewhere),
  * ``ref.py``    — the pure-jnp oracle tests assert against.

Kernels:
  * linked_matmul    — VO flagship: Matmul->Matmul operator linking (the
    SwiGLU MLP chain); the hidden activation lives in VMEM only.
  * linked_cbr_pool  — the paper's CBRA op (Conv1x1+BN+ReLU+AvgPool2x2
    fused; Figure 4's zigzag write order is the pool-block iteration).
  * split_matmul     — HO flagship: DOS §4.2.2 parameter split; every
    weight block is sized to VMEM (K/N/inC-chunked with accumulation).
  * decode_attention — GQA flash-decode for the serve_step hot loop.
  * fused_sampler    — sort-free top-k/top-p support filter for the
    serving sampler (binary-searched value thresholds; token-identical
    to the two-sort reference, which backend a ``KernelPlan`` picks).
"""

#: TPU vreg lane width: the last dim of a block tiles by it (the second to
#: last by 8) unless the block spans the array's own dim
LANE = 128


def interpret_mode() -> bool:
    """Whether the kernels run in the Pallas interpreter.

    True on the CPU backend, where tests and CPU runs check the kernels'
    semantics; False on a TPU, where they compile.  Any other backend
    raises: no kernel ever falls back to the interpreter in silence."""
    import jax
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(
            f"the Pallas kernels run on a TPU or, interpreted, on the CPU; "
            f"the default JAX backend is {backend!r}")
    return backend == "cpu"
