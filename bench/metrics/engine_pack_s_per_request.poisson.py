"""Mean length of the engine's ``repro.engine.pack`` span: one request's
boundary payload encoded and its transfer time computed, once per
request (``serving/engine.py``; device trace,
``bench/program_trace.py``)."""
from bench import program_trace


def read(run):
    return program_trace.mean_span_s(run, "repro.engine.pack")
