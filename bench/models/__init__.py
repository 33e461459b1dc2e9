"""Per-family adapters: a configuration file to the program under test."""
