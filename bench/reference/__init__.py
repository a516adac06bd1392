"""Plain float64 reference of the CF engine's semantics and the check
that decides ``correct``; imports nothing of the program."""
