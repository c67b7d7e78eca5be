"""Pallas TPU kernels for the hot ops (SURVEY.md §2 item 14).

Each kernel ships with a pure-XLA fallback used on non-TPU backends, so
the same graph runs under the CPU-mesh test harness.
"""

from reflow_tpu.kernels.topk import chunked_corpus_topk, fold_topk, topk

__all__ = ["topk", "fold_topk", "chunked_corpus_topk"]
