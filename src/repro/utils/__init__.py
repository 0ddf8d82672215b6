"""Small helpers shared across the package: the compile cache and the
names of the profiler spans and device scopes."""
