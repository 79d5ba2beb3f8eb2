"""Pallas TPU kernels (+ jnp oracles) for the perf-critical compute.

Each kernel directory holds:
  <name>.py — pl.pallas_call + explicit BlockSpec VMEM tiling, compiled
              for the TPU (``interpret=True`` runs the interpreter, the
              only mode on the CPU)
  ops.py    — the public wrapper (``use_pallas=False`` runs the oracle)
  ref.py    — the pure-jnp oracle

smallfloat_matmul — reduced-precision MAC array (paper §4.2)
conv2d_vmem       — weights-resident BraggNN conv (paper's no-BRAM result):
                    1×1 convs channel-major with the pixels in the lanes,
                    small 3×3 convs unrolled into one lane-dense
                    contraction, the rest as im2col
flash_attention   — blockwise attention (32k prefill path)
fused_softmax     — fused softmax incl. Taylor-exp mode (paper §3/§4.1)

``registry.py`` catalogues the four as pattern-matched fast paths
(``KERNELS``: nn-graph node -> kernel entry) plus the scalar-DFG opcode
table (``OPCODE_KERNELS``) — the tables the Pallas emission backend
(``repro.core.emit_pallas``) lowers through.
"""
