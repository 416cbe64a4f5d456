"""Plain float32 references, one module per configuration, and the
comparison that decides whether the system agrees with them."""
