"""The seam ``benchmark/program.py::re_kernel_forced`` holds on to.

The random-effect Newton system has one lowering (optim/newton.py) and no
option selects it. The benchmark's witness imports this module and swaps
``resolve_re_kernel`` while it reads the program; nothing under
``photon_tpu/`` reads either name. The ``benchmark`` issue of ROADMAP (a)
deletes ``re_kernel_forced`` and then this file.
"""

from __future__ import annotations

RE_KERNELS = ("auto", "xla")


def resolve_re_kernel(re_kernel: str) -> str:
    if re_kernel not in RE_KERNELS:
        raise ValueError(
            f"re_kernel must be one of {RE_KERNELS}, got {re_kernel!r}"
        )
    return "xla"
