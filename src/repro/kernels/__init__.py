"""Pallas TPU kernels for BINGO's compute hot spots.

Each kernel ships three files' worth of surface:
  * ``<name>.py``  — the ``pl.pallas_call`` + BlockSpec implementation
    (TPU is the target; validated in interpret mode on CPU);
  * ``ops.py``     — jit'd public wrappers with interpret-mode dispatch;
  * ``ref.py``     — pure-jnp oracles the tests ``assert_allclose`` against.

Kernels:
  * ``walk_fused``      — persistent whole-walk megakernel: the entire
    L-step walk in ONE launch, tables HBM-resident, per-step row DMAs
    double-buffered into VMEM (DESIGN.md §8 — the production walk path);
    its ``segment=True`` entry resumes walkers mid-walk with per-walker
    start steps and (vertex, step) frontier exits — the super-step
    relay's building block (``walk_segment``, DESIGN.md §10);
  * ``update_fused``    — batched-update megakernel: one §5.2
    insert→two-phase-delete→rebuild round in ONE launch, tables
    HBM-resident and aliased in place, affected rows DMA'd through
    double-buffered VMEM; bit-exact against ``core/updates.py``
    (DESIGN.md §9 — the production batched-update path);
  * ``walk_sample``     — fused hierarchical BINGO sampling, one step per
    launch (paper §4.1's O(1) sampling claim; node2vec proposals still
    run through it);
  * ``alias_build``     — batched Vose alias-table construction over the
    K-entry inter-group rows (paper §4.2's O(K) update claim);
  * ``radix_hist``      — Eq. 4 radix histograms W(p_k) for group rebuild;
  * ``flash_attention`` — blockwise attention for the LM zoo, which the
    walk engine does not use (DESIGN.md §4).
"""

from repro.kernels.ops import (alias_build, flash_attention, radix_hist,
                               update_fused, walk_fused, walk_sample,
                               walk_sample_uniform, walk_segment)

__all__ = ["walk_fused", "walk_segment", "update_fused", "walk_sample",
           "walk_sample_uniform", "alias_build", "radix_hist",
           "flash_attention"]
