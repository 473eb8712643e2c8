"""The benchmark of sparkrdma_tpu: whole shuffle jobs, run by
``perfbench/run.py`` as ``BENCHMARK.json`` describes them."""
