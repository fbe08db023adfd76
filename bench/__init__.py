"""The chip benchmark of the serving engine (see ``BENCHMARK.json``)."""
