"""Host clock around ``.compile()``: XLA and Mosaic on a first run, the read from the persistent
cache after (the cache's requests, hits and writes are on an earlier line).
"""


def read(trace, notes):
    return notes["compile_s"]
