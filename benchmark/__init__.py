"""The benchmark: everything ``BENCHMARK.json`` names lives under here."""
