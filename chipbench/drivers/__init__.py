"""chipbench.drivers: how a kind of cell runs.  The only files of the
benchmark that import the system under test."""
