"""photon-tpu's chip benchmark: see README.md here and BENCHMARK.json."""
