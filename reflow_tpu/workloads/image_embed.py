"""Benchmark config 5: image-embed ETL — ViT feature extract feeding an
incremental groupby-agg, sharded over the mesh.

BASELINE.md: "Image-embed ETL: ViT-B feature extract -> incremental
groupby-agg, sharded on a TPU v4-8". The graph is::

    images  source {image_id: uint8 [group_byte, *raw_pixels]}
    embed   Map(vit_forward)            -> f32 [group_id, *features]
    by_grp  GroupBy(key=group, value=features)
    cent    Reduce('mean')              {group: centroid}

Under the ShardedTpuExecutor this is data-parallel model inference: the
per-tick image deltas are row-sharded over the mesh, each shard runs the
(pure) ViT forward on its slice inside the shard_map'd tick, and the
centroid Reduce combines cross-shard with one psum_scatter — the
groupby-agg never leaves the device.

An image moving between groups (or being deleted) is an ordinary
retract/insert delta pair; the mean's retract-old/insert-new emission
keeps every centroid exact, not approximate.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import numpy as np

from reflow_tpu.delta import DeltaBatch, Spec
from reflow_tpu.graph import FlowGraph, Node
from reflow_tpu.models import vit_forward


@dataclasses.dataclass
class ImageEmbedGraph:
    graph: FlowGraph
    images: Node     # source
    centroids: Node  # read_table -> {group: mean feature vector}


def pixels_to_input(px):
    """uint8 pixels -> the model's [-1, 1] float input.

    One definition shared by the device Map and the host oracle so the
    differential tests compare the same forward pass. Works on numpy and
    jax arrays alike.
    """
    return px.astype("float32") * np.float32(2.0 / 255.0) - np.float32(1.0)


def build_graph(n_images: int, n_groups: int, params: Dict,
                model_axis: Optional[str] = None) -> ImageEmbedGraph:
    """``model_axis`` (VERDICT r4 #8): tensor-parallel the ViT over that
    mesh axis — params shard per ``vit_param_specs`` (run under
    ``ShardedTpuExecutor(mesh, model_axis=...)`` on a (delta, model)
    mesh) and the Map runs ``vit_forward_tp`` (two psums per block).
    A model too large for one chip's HBM then holds 1/m of its weights
    per device while deltas stay row-sharded on the delta axis."""
    import jax.numpy as jnp

    cfg = params["_cfg"]
    flat = cfg["img"] * cfg["img"] * cfg["chans"]
    dim = cfg["dim"]
    f32 = np.float32
    if n_groups > 256:
        raise ValueError("group id rides in the row's leading uint8 byte; "
                         "n_groups must be <= 256 (ids 0-255)")
    g = FlowGraph("image_embed")
    # rows ship as RAW uint8 [group_byte | pixels] — what a real ETL
    # ingests, and 4x less host->device traffic than f32 pixels
    src = g.source("images", Spec((1 + flat,), np.uint8, key_space=n_images))

    # weights ride as op params (compiled-program ARGUMENTS: VERDICT r2 #2
    # — closing over them traced ~86M ViT-B floats into a ~350MB HLO and
    # meant full recompilation on any weight change); only the static
    # shape-driving config is closed over
    weights = {k: v for k, v in params.items() if k != "_cfg"}
    param_specs = None
    if model_axis is not None:
        from reflow_tpu.models.vit import vit_forward_tp, vit_param_specs

        param_specs = vit_param_specs(cfg, model_axis)

        def embed(p, v):
            feats = vit_forward_tp({**p, "_cfg": cfg},
                                   pixels_to_input(v[:, 1:]),
                                   axis=model_axis)
            return jnp.concatenate([v[:, :1].astype(jnp.float32), feats],
                                   axis=-1)
    else:
        def embed(p, v):  # (weights, [C, 1+flat] u8) -> [C, 1+dim] f32
            feats = vit_forward({**p, "_cfg": cfg},
                                pixels_to_input(v[:, 1:]))
            return jnp.concatenate([v[:, :1].astype(jnp.float32), feats],
                                   axis=-1)

    emb = g.map(src, embed, vectorized=True, params=weights,
                param_specs=param_specs,
                spec=Spec((1 + dim,), f32, key_space=n_images), name="embed")
    by_grp = g.group_by(emb, key_fn=lambda k, v: v[0],
                        value_fn=lambda k, v: v[1:],
                        spec=Spec((dim,), f32, key_space=n_groups),
                        name="by_group")
    cent = g.reduce(by_grp, "mean", name="centroids")
    return ImageEmbedGraph(g, src, cent)


# -- host boundary: image stream driver ------------------------------------

class ImageStream:
    """Host mirror: images with group assignments, delta generation."""

    def __init__(self, params: Dict, seed: int = 0):
        self.cfg = params["_cfg"]
        self.params = params
        self.rng = np.random.default_rng(seed)
        self.images: Dict[int, np.ndarray] = {}   # id -> flat pixels
        self.groups: Dict[int, int] = {}          # id -> group

    def _flat(self) -> int:
        return self.cfg["img"] * self.cfg["img"] * self.cfg["chans"]

    def _row(self, i: int) -> np.ndarray:
        return np.concatenate(
            [[np.uint8(self.groups[i])], self.images[i]]).astype(np.uint8)

    def insert(self, ids, groups) -> DeltaBatch:
        rows = []
        for i, grp in zip(ids, groups):
            self.images[int(i)] = self.rng.integers(
                0, 256, size=self._flat(), dtype=np.uint8)
            self.groups[int(i)] = int(grp)
            rows.append(self._row(int(i)))
        return DeltaBatch(np.asarray(ids, np.int64), np.stack(rows),
                          np.ones(len(rows), np.int64))

    def move(self, i: int, new_group: int) -> DeltaBatch:
        """Reassign an image's group: retract old row, insert new."""
        old = self._row(i)
        self.groups[i] = int(new_group)
        new = self._row(i)
        return DeltaBatch(np.array([i, i], np.int64), np.stack([old, new]),
                          np.array([-1, 1], np.int64))

    def delete(self, i: int) -> DeltaBatch:
        row = self._row(i)
        del self.images[i], self.groups[i]
        return DeltaBatch(np.array([i], np.int64), row[None],
                          -np.ones(1, np.int64))

    def reference_centroids(self) -> Dict[int, np.ndarray]:
        """Oracle: same forward pass, float64 group means."""
        if not self.images:
            return {}
        ids = sorted(self.images)
        feats = np.asarray(vit_forward(
            self.params,
            pixels_to_input(np.stack([self.images[i] for i in ids]))))
        out: Dict[int, list] = {}
        for i, f in zip(ids, feats):
            out.setdefault(self.groups[i], []).append(f.astype(np.float64))
        return {g: np.mean(v, axis=0) for g, v in out.items()}
