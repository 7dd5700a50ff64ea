"""Plain float32 references, one per model family, named by each
configuration file's ``reference`` key."""
