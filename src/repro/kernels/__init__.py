"""Pallas TPU kernels for the distance hot-spots.

Each kernel ships three layers:
  * ``<name>.py`` — ``pl.pallas_call`` + explicit BlockSpec VMEM tiling,
  * ``ops.py``    — jit'd dispatching wrappers (TPU: compiled kernel,
                    CPU: jnp reference; ``use_pallas=True`` forces the
                    interpreted kernel for validation),
  * ``ref.py``    — pure-jnp oracles the tests sweep against.

Kernels:
  * ``distance``    — tiled pairwise distances (MXU GEMM for l2/ip/cosine,
                      VPU strips for l1/chi2).
  * ``gather_dist`` — fused gather+distance with scalar-prefetched candidate
                      ids and double-buffered HBM→VMEM row DMAs.
  * ``expand``      — the fused EHC expansion step (Alg. 1/3 inner loop):
                      candidate-row DMAs + visited-hash probe/record + beam
                      top-k merge in one kernel, with the bit-identical
                      pure-jnp ``expand_reference`` beside it
                      (``ops.expand_step`` is the three-way dispatcher).
  * ``visited_lookup`` — the LGD commit's D(q, x) from each wave lane's
                      visited-hash table as a dense compare on the vector
                      unit, in place of per-id probe gathers
                      (``ops.visited_lookup``).
"""

from repro.kernels import expand, ops, ref

__all__ = ["expand", "ops", "ref"]
