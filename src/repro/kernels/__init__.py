"""Pallas TPU kernels for the dehazing hot spots + jnp oracles.

Modules:
  dark_channel  — fused channel-min + separable windowed-min (DCP Eq. 3)
  boxfilter     — running-sum separable box filter (guided-filter core)
  recover       — fused haze-free recovery epilogue (Eq. 8)
  atmolight     — argmin-t / robust top-k atmospheric light reduction
                  (Eq. 5/6) + the shared in-VMEM top-k running selection;
                  the XLA form (``ref``) is a first-index argmin reduction
                  at k = 1 and ``lax.top_k`` at k > 1
  fused         — single-pass DCP/CAP megakernels (Eq. 3/4+5/6+9+8 in one
                  launch), incl. the halo-aware variant for height- and/or
                  width-sharded meshes (2-D validity masking)
  tuning        — block-size/tiling registry + autotune sweep
  ops           — jitted dispatch wrappers (ref | pallas | interpret | fused)
  ref           — pure-jnp oracles for all of the above
"""
from repro.kernels import ops, ref, tuning  # noqa: F401
