"""Seconds from process start to the start of the measured window:
data generation, building the experiment, compilation or its retrieval
from the persistent cache, and the first rounds."""


def read(ctx):
    return ctx["setup_s"]
