"""The on-chip benchmark of localai-tpu: see benchmark/README.md."""
