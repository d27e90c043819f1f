"""Native host runtime loader.

Compiles host_runtime.cc with the system toolchain on first import
(cached as a .so next to the source, keyed by the source, the compiler
flags and the machine it was built on) and exposes
it via ctypes. Importers must tolerate ImportError: every native entry
point has a pure-numpy fallback, so a missing compiler only costs speed
(the reference hard-requires its C++ runtime; ours degrades).
"""

from pixie_tpu.native import host_runtime

__all__ = ["host_runtime"]
