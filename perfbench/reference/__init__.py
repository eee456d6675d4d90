"""Plain references, one per model family."""
