"""What happens when a Pallas kernel cannot take a shape.

Compiled (the TPU path), that is an error: a jnp oracle standing in for a
kernel on the chip would change every measurement without notice.
Interpreted (CPU tests), the jnp twin runs and a
:class:`KernelFallbackWarning` says so; ``warnings.simplefilter("error",
KernelFallbackWarning)`` turns those into errors too.
"""

from __future__ import annotations

import warnings


class KernelFallbackWarning(UserWarning):
    """A Pallas backend ran a jnp implementation for an untileable shape."""


def kernel_fallback(problem: str, *, interpret: bool) -> None:
    """Raise (compiled kernels) or warn (interpret mode) that a shape falls
    off a Pallas kernel; ``problem`` names the kernel and its constraint."""
    if not interpret:
        raise NotImplementedError(
            f"{problem}: the compiled Pallas kernel cannot take this shape; "
            f"call repro.kernels.set_backend('ref') to run the jnp path "
            f"deliberately"
        )
    warnings.warn(
        f"{problem}: running the jnp twin instead", KernelFallbackWarning,
        stacklevel=3,
    )
