"""Runners, one per kind of configuration (the configuration file names its kind)."""
