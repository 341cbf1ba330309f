"""Client seconds of the readers' scan tasks (``MeshReader.stats()``
``client_cpu_s``) per million tokens delivered in the traced window."""

from perfbench import readers


def read(r):
    c = r.counters
    return readers.per(c["reader_client_cpu_s"], c["tokens"], 1e6)
