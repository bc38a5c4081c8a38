"""The benchmark of mxnet-tpu: see README.md and ../BENCHMARK.json."""
