"""Share of the aggregate folds of the traced window (one per row group)
that the decode plane (``aformat/decode.py``, ``DecodeBackend.fold``)
ran on the fold kernel, from each fold's ``report["fold"]``.  None where
no report holds a fold (a program without the hook)."""


def read(r):
    folds = [rep["fold"] for rep in r.counters.get("reports", [])
             if "fold" in rep]
    if not folds:
        return None
    return 100.0 * sum(f == "kernel" for f in folds) / len(folds)
