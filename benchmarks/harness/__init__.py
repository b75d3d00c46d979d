"""The yardstick: everything a run of a cell needs besides the system under
test. Later PRs change the program, not these files."""
