# Device-kernel layer.  Entry-point convention: every kernel is reached
# through kernels/registry.py (dispatch + autotune + fallback); each
# kernel package keeps <name>.py / ref.py where ref.py is the pure
# oracle its implementations are validated against.
#   expand/    — fused frontier expansion (fused Pallas | XLA chain)
#   fold/      — fused replay/splice/merge FOLD step (Pallas | XLA chain)
#   emit/      — fused valid-row EMIT pack (Pallas | XLA chain)
#   leapfrog/  — batched bounded lower/upper bound (Pallas dense count)
#   flash_attention/ — LM-substrate attention (own ops.py facade)


def interpret_default() -> bool:
    """Whether a Pallas kernel runs in the interpreter when its caller does
    not say: only where the backend has no Pallas compiler (CPU)."""
    import jax

    return jax.default_backend() not in ("tpu", "gpu")
