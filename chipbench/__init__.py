"""chipbench — the yardstick of BENCHMARK.json (see chipbench/README.md).

Importing this package imports neither JAX nor ``dynamo_tpu``: the load
generator is a child process that must stay off the chip and off the
server's interpreter lock.
"""
