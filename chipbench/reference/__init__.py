"""Plain float32 references, one module per architecture, found by the
``architecture`` key of a configuration file.  They import nothing of the
program under test."""
