"""Plain references.  Nothing under this package imports the program."""
