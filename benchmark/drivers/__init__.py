"""One module per job kind: ``run(ctx) -> observations``."""
