"""Plain references, written without the program under test."""
