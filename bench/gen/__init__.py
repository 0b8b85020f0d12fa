"""Seeded instance generators, one module per generator name in a config."""
