"""Cell runners, one module per ``task`` a configuration file names."""
