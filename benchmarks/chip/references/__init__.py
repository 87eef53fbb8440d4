"""Plain references, one module per kind of model, named by the
``reference`` key of a configuration file."""
