"""Plain float32 references, one module per architecture, named by the
``reference`` key of a configuration file. Each imports nothing of the
program under test."""
