"""The benchmark: BENCHMARK.json's command, harness, configurations, traffic
mixes, entries, per-layer readers and plain references (see PERF.md)."""
