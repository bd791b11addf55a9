"""The benchmark's general machinery: deployments, traffic, drivers,
the plain reference and the trace reduction. Everything that belongs to
one configuration, traffic mix, per-layer metric or kernel lives in a
file of its own under ``bench/`` and is found by name."""
