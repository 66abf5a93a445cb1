"""Inference-model serialization format.

Reference: paddle.static.save/load_inference_model
(python/paddle/static/io.py) producing __model__ (ProgramDesc) + params; the
runtime that consumes them is the 59k-LoC AnalysisPredictor stack
(paddle/fluid/inference/api/analysis_predictor.h:87 — load, optimize,
zero-copy run).

TPU-native format: the compiled artifact is a serialized jax.export
StableHLO function  fn(weights..., feeds...) -> fetches  plus a weights blob
and a JSON manifest. "Optimization passes" are XLA's job at load time; the
predictor's zero-copy contract is device-resident weights placed once and
feed/fetch buffers exchanged without host round-trips.

Files written for prefix P:
  P.pdmodel     — serialized StableHLO (jax.export blob)
  P.pdiparams   — npz of weight arrays (w0..wN in call order)
  P.manifest.json — feed names/shapes/dtypes, fetch count, format version
"""
from __future__ import annotations

import io
import json
import os
from typing import List, Sequence

import numpy as np

FORMAT_VERSION = 1


def _write_triple(serialized: bytes, weight_vals: Sequence, manifest: dict,
                  path_prefix: str) -> str:
    """The on-disk format, in ONE place: .pdmodel StableHLO blob +
    .pdiparams npz (w{i} in call order) + .manifest.json."""
    os.makedirs(os.path.dirname(os.path.abspath(path_prefix)) or ".",
                exist_ok=True)
    with open(path_prefix + ".pdmodel", "wb") as f:
        f.write(serialized)
    buf = io.BytesIO()
    np.savez(buf, **{f"w{i}": np.asarray(w)
                     for i, w in enumerate(weight_vals)})
    with open(path_prefix + ".pdiparams", "wb") as f:
        f.write(buf.getvalue())
    with open(path_prefix + ".manifest.json", "w") as f:
        json.dump(manifest, f, indent=2)
    return path_prefix + ".pdmodel"


def export_inference_artifact(fn, weight_vals: Sequence, feed_specs,
                              path_prefix: str):
    """Export fn(weights_list, feeds_list) -> fetches and write the triple.

    feed_specs: list of (name, shape, dtype-str).
    """
    import jax
    from jax import export  # a lazy submodule: `jax.export.X` needs this
    w_avals = [jax.ShapeDtypeStruct(np.shape(w), np.asarray(w).dtype)
               for w in weight_vals]
    # None / -1 feed dims export as SYMBOLIC dims (shape polymorphism): the
    # served model accepts any batch size, like the reference's -1 dims.
    # All LEADING dynamic dims share ONE symbol: multi-feed models (ids +
    # mask, image + shape-info) combine their feeds along batch, and
    # independent symbols would make that combination inconclusive at
    # trace time. Non-leading dynamic dims stay independent.
    scope = export.SymbolicScope()
    f_avals = []
    sym_count = 0
    for _, s, d in feed_specs:
        parts = []
        any_sym = False
        for i, dim in enumerate(s):
            if dim is None or (isinstance(dim, int) and dim < 0):
                any_sym = True
                if i == 0:
                    parts.append("batch")
                else:
                    parts.append(f"d{sym_count}")
                    sym_count += 1
            else:
                parts.append(str(int(dim)))
        if any_sym:
            shape = export.symbolic_shape(
                ", ".join(parts), scope=scope)
        else:
            shape = tuple(int(x) for x in s)
        f_avals.append(jax.ShapeDtypeStruct(shape, np.dtype(d)))

    def flat(*args):
        ws = list(args[:len(w_avals)])
        fs = list(args[len(w_avals):])
        return fn(ws, fs)

    # export for both platforms: train-on-TPU / serve-anywhere (and vice
    # versa) is the deployment contract
    exported = export.export(
        jax.jit(flat), platforms=("cpu", "tpu"))(*w_avals, *f_avals)
    manifest = {
        "format": "paddle_tpu_inference",
        "version": FORMAT_VERSION,
        "n_weights": len(w_avals),
        "feeds": [{"name": n, "shape": list(s), "dtype": str(d)}
                  for n, s, d in feed_specs],
        "n_fetches": len(exported.out_avals),
    }
    return _write_triple(exported.serialize(), weight_vals, manifest,
                         path_prefix)


class InferenceArtifact:
    """Deserialized artifact: StableHLO executable + device-placed weights."""

    def __init__(self, exported, weights: List, manifest: dict):
        self.exported = exported
        self.weights = weights  # device arrays, call order
        self.manifest = manifest
        self.feed_names = [f["name"] for f in manifest["feeds"]]
        self.feed_specs = {f["name"]: (tuple(f["shape"]), f["dtype"])
                           for f in manifest["feeds"]}
        self.n_fetches = manifest["n_fetches"]

    @classmethod
    def load(cls, path_prefix: str):
        import jax.numpy as jnp
        from jax import export

        with open(path_prefix + ".pdmodel", "rb") as f:
            exported = export.deserialize(bytearray(f.read()))
        with open(path_prefix + ".manifest.json") as f:
            manifest = json.load(f)
        with open(path_prefix + ".pdiparams", "rb") as f:
            z = np.load(io.BytesIO(f.read()))
            weights = [jnp.asarray(z[f"w{i}"])
                       for i in range(manifest["n_weights"])]
        return cls(exported, weights, manifest)

    def run(self, feed_vals: Sequence):
        """feed_vals in manifest feed order (device or host arrays)."""
        import jax.numpy as jnp

        args = list(self.weights) + [jnp.asarray(v) for v in feed_vals]
        out = self.exported.call(*args)
        return list(out) if isinstance(out, (tuple, list)) else [out]

    def save(self, path_prefix: str) -> str:
        """Re-serialize this artifact to a new prefix (same triple)."""
        return _write_triple(self.exported.serialize(), self.weights,
                             self.manifest, path_prefix)
