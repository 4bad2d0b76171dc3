"""The benchmark: BENCHMARK.json's yardstick.  See README.md."""
