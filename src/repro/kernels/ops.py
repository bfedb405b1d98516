"""Public kernel entry points: the compiled Pallas TPU kernels.

``flash_attention`` / ``rmsnorm`` run the compiled Pallas kernel
(``interpret=False`` by default).  A caller without a TPU passes
``interpret=True`` itself (the CPU tests do), which executes the kernel body
in Python; nothing switches to it behind the caller's back.  No model layer
calls these yet: ``layers.mha`` and ``layers.rms_norm`` use their jnp forms.
"""

from repro.kernels.flash_attention import flash_attention
from repro.kernels.ref import mha_reference, rmsnorm_reference
from repro.kernels.rmsnorm import rmsnorm

__all__ = ["flash_attention", "rmsnorm", "mha_reference", "rmsnorm_reference"]
