"""Traffic generators, one module per ``kind`` a mix's JSON file names."""
