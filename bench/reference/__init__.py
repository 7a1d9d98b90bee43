"""Plain references, independent of the system under test: they import
nothing of it and take nothing it made."""
