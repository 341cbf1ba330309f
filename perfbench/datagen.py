"""Seeded data from a configuration's column specs, in NumPy alone.

A column spec is a JSON object: ``{"name", "type", "draw", ...}``, where
``draw`` names one of the distributions below and the other keys are its
parameters.  Optional keys, applied in this order: ``round`` rounds to
that many decimals (cents: 2, whole seconds: 0), ``times`` multiplies
(seconds to microseconds: 1000000), and ``nonzero_share`` keeps that
share of the draws and sets the rest to 0, as fees and tolls that most
trips do not pay.  A ``sum`` column adds up the columns named in ``of``
and, with ``plus``, one more draw of a nested spec: a drop-off time is
the pick-up time plus a duration, a total the sum of its parts.

The same ``(seed, stream)`` gives the same arrays.  Each object or shard
draws from its own stream, so they can be made in any order, or side by
side in threads.  Nothing here imports the system under test: the plain
references are built from these arrays.
"""

from __future__ import annotations

import numpy as np

DTYPES = {"int32": np.int32, "int64": np.int64, "float32": np.float32,
          "float64": np.float64, "string": object}


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """The generator of one stream of a run's data (any whole ``seed``)."""
    return np.random.default_rng([seed % (1 << 64), *stream])


def draw(rng: np.random.Generator, spec: dict, n: int,
         columns: dict[str, np.ndarray] | None = None) -> np.ndarray:
    """``n`` values of one column spec, in the spec's type; ``columns``
    holds the columns a ``sum`` adds up."""
    kind = spec["draw"]
    if kind == "integers":
        out = rng.integers(spec["low"], spec["high"], n)
    elif kind == "gamma":
        out = rng.gamma(spec["shape"], spec["scale"], n)
    elif kind == "beta":
        out = rng.beta(spec["a"], spec["b"], n)
    elif kind == "poisson":
        out = np.maximum(spec.get("min", 0), rng.poisson(spec["lam"], n))
    elif kind == "choice":
        out = rng.choice(np.asarray(spec["values"]), n, p=spec.get("p"))
    elif kind == "constant":
        out = np.full(n, spec["value"])
    elif kind == "sum":
        out = sum(columns[c] for c in spec["of"])
        if "plus" in spec:
            out = out + draw(rng, spec["plus"], n)
    else:
        raise ValueError(f"unknown draw {kind!r} in column spec {spec}")
    if "round" in spec:
        out = np.round(out, spec["round"])
    if "times" in spec:
        out = out * spec["times"]
    out = out.astype(DTYPES[spec.get("type", "float64")])
    share = spec.get("nonzero_share")
    if share is not None:
        out = out * (rng.random(n) < share).astype(out.dtype)
    return out


def table_columns(specs: list[dict], n: int, seed: int, *stream: int
                  ) -> dict[str, np.ndarray]:
    """One object's columns, in spec order, from stream ``stream``: the
    drawn ones first, in spec order, then the sums."""
    rng = rng_for(seed, *stream)
    cols: dict[str, np.ndarray] = {}
    for s in sorted(specs, key=lambda s: s["draw"] == "sum"):
        cols[s["name"]] = draw(rng, s, n, cols)
    return {s["name"]: cols[s["name"]] for s in specs}


def corpus_shard(cfg: dict, seed: int, shard: int
                 ) -> dict[str, np.ndarray]:
    """One shard of a token corpus, exactly ``row_groups_per_shard *
    row_group_rows`` token rows: documents of ``doc_len`` tokens (the last
    one cut at the shard's end), per-document columns repeated on every
    token row, and ``pos``, the token's place in its document.  Document
    ids of shard ``s`` start at ``s * rows``, so shards need no order."""
    rows = cfg["row_groups_per_shard"] * cfg["row_group_rows"]
    rng = rng_for(seed, 0, shard)
    lens = draw(rng, cfg["doc_len"], 2 * rows // cfg["doc_len"]["lam"] + 16)
    end = int(np.searchsorted(np.cumsum(lens), rows))
    lens = lens[:end + 1].astype(np.int64)
    lens[-1] -= int(lens.sum()) - rows
    docs = len(lens)
    starts = np.repeat(np.cumsum(lens) - lens, lens)
    cols = {"doc_id": np.repeat(np.arange(shard * rows, shard * rows + docs),
                                lens).astype(np.int64),
            "pos": (np.arange(rows) - starts).astype(np.int32),
            "token": draw(rng, cfg["token"], rows)}
    for spec in cfg["doc_columns"]:
        cols[spec["name"]] = np.repeat(draw(rng, spec, docs), lens)
    return cols
