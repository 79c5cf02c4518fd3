"""Plain references: numpy only, importing nothing from the code under test."""
