"""The on-chip benchmark of the FedSiKD runtime (see PERF.md)."""
