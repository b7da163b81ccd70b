"""Serving benchmark for the TPU: see bench/run.py."""
