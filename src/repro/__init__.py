"""repro: worst-case-optimal join processing for graph patterns on TPU.

x64 is enabled package-wide: join counts are exact int64 on device (the
paper's benchmark outputs overflow int32 at Pokec/LiveJournal scale).
Model code uses explicit bf16/f32 dtypes throughout, so the x64 default
only affects the integer join/count paths.  Pallas kernel bodies are
traced with x64 off (``kernels/backend.py``): the TPU kernel compiler has
no 64-bit types.  Opt out with ``REPRO_X64=0``.
"""
import os as _os

import jax as _jax

if _os.environ.get("REPRO_X64", "1") == "1":
    _jax.config.update("jax_enable_x64", True)

__version__ = "1.0.0"
