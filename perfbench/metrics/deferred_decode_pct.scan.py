"""Share of the column chunks the decode plane (``aformat/decode.py``)
decoded over every row group of the traced window that it deferred past
the selection, decoding the kept rows' codes alone.  None where no
routing report lists what it deferred (a program without deferral)."""


def read(r):
    chunks = deferred = 0
    for rep in r.counters.get("reports", []):
        if "deferred" in rep:
            chunks += len(rep["columns"])
            deferred += len(rep["deferred"])
    if not chunks:
        return None
    return 100.0 * deferred / chunks
