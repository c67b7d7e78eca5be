"""The five reference benchmark workloads (SURVEY.md §2 item 12 /
BASELINE.md), plus one beyond-spec demo:

1. ``wordcount``   — incremental word-count (Map→Reduce, CPU default path)
2. ``tfidf``       — streaming TF-IDF (Map / GroupBy / Reduce)
3. ``pagerank``    — incremental PageRank (iterative Join + Reduce; north star)
4. ``knn``         — k-NN re-index (vmapped cosine + Pallas top-k)
5. ``image_embed`` — ViT-B feature extract → incremental groupby-agg
6. ``sssp``        — incremental single-source shortest paths (min-plus
                     Join + min-Reduce fixpoint; beyond the spec)

and two public benchmarks of the incremental-view field: ``nexmark``
(Q3 / Q4: two stream joins, max into mean) and ``tpch`` (Q3 under the
refresh functions: a join that feeds a join, inserts and deletes).
"""
