"""Plain references: the answers each configuration must give, from the
generator's own arrays, in NumPy alone."""
