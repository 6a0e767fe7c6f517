"""How the benchmark builds the program under test for one architecture:
its configuration object and its weights, from a configuration file.
One module per ``architecture`` key."""
