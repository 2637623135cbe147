"""The toy cells that stand for each chip cell in the harness tests.

``benchroot.make_root`` rewrites the ``workloads`` list of each metric in
``BENCHMARK.json`` through ``benchroot.CELLS``, which maps a chip cell to
the toy cells that take its place.  Chip cells added after that map was
written are mapped here: ``deepseek-7b.decode``, the backlog on an MHA
model, is stood for by ``tiny-mha.decode``.
"""
import benchroot

benchroot.CELLS.setdefault("deepseek-7b.decode", ["tiny-mha.decode"])
