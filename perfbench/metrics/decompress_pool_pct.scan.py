"""Share of the compressed bytes of every row group of the traced window
that the decode plane (``aformat/decode.py``) inflated on its shared pool
rather than on the scanning task's thread.  None where no routing report
says where its buffers were inflated."""


def read(r):
    pool = inline = 0
    for rep in r.counters.get("reports", []):
        sizes = rep.get("decompress")
        if sizes is not None:
            pool += sizes["pool_bytes"]
            inline += sizes["inline_bytes"]
    if not pool + inline:
        return None
    return 100.0 * pool / (pool + inline)
