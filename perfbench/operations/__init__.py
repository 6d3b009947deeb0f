"""Operations functions, one file a model: ``layers(config, runner)``
returns the model's matrix products as ``harness/flops.py::Layer`` rows.
A configuration file names its own under ``operations``; one that names
none has no FLOP or roofline metric to report."""
