"""Mean length of the engine's ``repro.engine.pull`` span: the copy of a
group's latent and context from the device to the host, once per group
(``serving/engine.py``; device trace, ``bench/program_trace.py``)."""
from bench import program_trace


def read(run):
    return program_trace.mean_span_s(run, "repro.engine.pull")
