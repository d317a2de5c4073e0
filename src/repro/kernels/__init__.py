"""Pallas TPU kernels for the framework's compute hot spots.

- rff:             fused RFF feature map (paper Def. 2) — matmul + cos/sin epilogue
- centered_gram:   Sigma H Sigma^T for RF-TCA (Alg. 1) with fused centering
- rff_gram_stream: one-pass fused featurize + Gram/moment accumulation —
                   Sigma never hits HBM, peak memory O(N^2 + N b) regardless
                   of the sample count n (the RF-TCA scaling claim); past
                   N ~ 1k, or at a p too wide for the untiled blocks, it
                   switches to an (i, j) output-tiled grid whose
                   per-instance VMEM is bounded by the tile, not N
- flash_attention: blockwise online-softmax GQA attention (causal / window)
- segment_reduce:  weighted segment sums for the two-tier fleet plane's
                   grouped moment merges — the (E, K) membership x weights
                   matrix contracted against stacked payloads on the MXU

Each has a jit wrapper in ops.py and a pure-jnp oracle in ref.py. On TPU
they lower via Mosaic; on CPU (the tests) they run in interpret mode.
The streaming RF-TCA fit (core.rf_tca) uses an XLA lax.scan with the same
memory profile on non-TPU backends, where interpret-mode Pallas is slow.
"""
from repro.kernels import ops, ref
