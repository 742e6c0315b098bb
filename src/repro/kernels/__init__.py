"""Pallas TPU kernels for the compute hot-spots the paper optimizes.

- ``its_select``  — fused CTPS build + ITS + BRS retry (SELECT, DESIGN.md §2)
- ``walk_step``   — segment-DMA weighted walk transition (DESIGN.md §6)
- ``alias_select`` — O(1) alias-table walk transition (DESIGN.md §13)
- ``common``      — prefix sum, ITS pick and walker tiles shared with oracles
- ``ops``         — jit'd wrappers owning RNG and shape plumbing
- ``ref``         — pure-jnp oracles consuming the same random budgets

Kernels run compiled through Mosaic on TPU and fall back to ``interpret=True``
elsewhere (``resolve_interpret`` — the CPU/test path; pass ``interpret=False``
to require the chip); the selection backend dispatcher in
``repro.core.backend`` decides when the engine uses them at all.
"""
from repro.kernels.common import resolve_interpret
from repro.kernels.its_select import its_select_pallas
from repro.kernels.walk_step import (
    pad_csr_for_kernel,
    walk_step_pallas,
    walk_step_window_pallas,
)

__all__ = [
    "its_select_pallas",
    "walk_step_pallas",
    "walk_step_window_pallas",
    "pad_csr_for_kernel",
    "resolve_interpret",
]
