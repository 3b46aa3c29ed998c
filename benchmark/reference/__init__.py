"""Plain references: one file per architecture, float32, no kernels."""
