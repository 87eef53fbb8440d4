"""Pallas TPU kernels for SOCKET's perf-critical paths.

* socket_score    — the paper's CUDA scoring kernel, TPU-adapted
                    (bit-packed streaming + factorized corner softmax,
                    DESIGN.md §2).
* flash_decode    — online-softmax GQA decode over the gathered top-k
                    subset (the paper's Triton Flash-Decode analogue).
* flash_prefill   — causal flash-attention forward for the dense prefill.
* paged_attention — fused score→select→attend over the serving engine's
                    block table (one pass over the paged pool, no score
                    / index / gathered-K/V materialization in HBM).

Each kernel ships ``ops.py`` (jitted wrapper; Mosaic on a TPU backend,
the Pallas interpreter elsewhere — ``common.resolve_interpret``) and
``ref.py`` (pure-jnp oracle driven by ``tests/kernel_harness.py``).
See README.md in this directory for the layout contract.
"""

from repro.kernels import (flash_decode, flash_prefill, paged_attention,
                           socket_score)

__all__ = ["flash_decode", "flash_prefill", "paged_attention",
           "socket_score"]
