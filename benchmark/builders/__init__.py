"""One module per kind of system under test. A configuration file names its
builder as ``"builder": "<module>"`` (a module of this package) and the
harness calls ``build(config, traffic, seed, rehearse)`` in it; a new
family of models is a new module here, never an edit."""
