"""Pallas TPU kernels for the framework's compute hot-spots.

The paper itself is a control-plane contribution (no kernel-level claims)
so these kernels serve the *framework*: flash attention (GQA/causal/SWA),
the Mamba-2 SSD intra-chunk kernel, and a fused RMSNorm. Each directory
has <name>.py (pl.pallas_call + BlockSpec), ops.py (jit'd wrapper) and
ref.py (pure-jnp oracle). Off the TPU an ops wrapper runs only when
the caller passes ``interpret=True`` (the tests do); otherwise it raises,
so no program silently runs a kernel in the Pallas interpreter.
"""

import jax


def check_backend(interpret: bool) -> None:
    """Raise unless the kernel can run as asked: compiled on a TPU, or
    in the Pallas interpreter because the caller said so."""
    if not interpret and jax.default_backend() != "tpu":
        raise RuntimeError(
            f"Pallas TPU kernel called on the {jax.default_backend()!r} "
            f"backend; pass interpret=True to run it in the interpreter")
