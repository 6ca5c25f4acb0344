"""The benchmark's harness: everything a cell's run needs besides the
configuration, traffic and per-layer-metric files it finds by name."""
