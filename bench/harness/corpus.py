"""The corpus and the query pool of a configuration, made from ``--seed``.

A clustered Gaussian mixture with categorical attributes, at the sizes the
configuration file states. Its shape follows ``make_hybrid_dataset`` of the
program (``repro.data.synthetic``, the ``sift`` profile), copied here so that
a change to the program cannot change the benchmark's data. Queries are
fresh draws from random clusters, not perturbed rows, with attributes drawn
under the same attribute-cluster correlation as the rows.
"""
from __future__ import annotations

import dataclasses

import numpy as np


def rng(seed: int, stream: int) -> np.random.Generator:
    """Independent streams of one run: 0 corpus, 1 traffic, 2 sampling.
    Any whole number is a seed, negative or past 64 bits included."""
    return np.random.default_rng([int(seed) % 2**64, stream])


@dataclasses.dataclass
class Corpus:
    features: np.ndarray  # (N, M) float32
    attrs: np.ndarray  # (N, L) int32, labels 0..labels-1
    query_features: np.ndarray  # (Q, M) float32
    query_attrs: np.ndarray  # (Q, L) int32


def _attrs(r, n, dims, labels, centre_attrs, assign, corr):
    out = r.integers(0, labels, size=(n, dims), dtype=np.int32)
    if corr > 0.0:
        copy = r.random((n, dims), dtype=np.float32) < corr
        out = np.where(copy, centre_attrs[assign], out)
    return out.astype(np.int32)


def make_corpus(spec: dict, n_queries: int, seed: int) -> Corpus:
    """``spec`` is the configuration's ``corpus`` block."""
    if spec.get("profile", "sift") != "sift":
        raise ValueError(f"no corpus profile {spec['profile']!r}: only "
                         "'sift' is made here")
    r = rng(seed, 0)
    n, dim, k = spec["rows"], spec["dim"], spec["clusters"]
    scale, spread = spec["noise_scale"], spec["cluster_spread"]
    dims, labels = spec["attr_dims"], spec["labels_per_dim"]
    corr = spec["attr_cluster_corr"]

    centres = r.normal(0.0, scale * spread, size=(k, dim)).astype(np.float32)
    centre_attrs = r.integers(0, labels, size=(k, dims), dtype=np.int32)

    def draw(m):
        assign = r.integers(0, k, size=m)
        x = r.standard_normal(size=(m, dim), dtype=np.float32)
        x *= np.float32(scale)
        x += centres[assign]
        return x, _attrs(r, m, dims, labels, centre_attrs, assign, corr)

    features, attrs = draw(n)
    qf, qa = draw(n_queries)
    return Corpus(features, attrs, qf, qa)
