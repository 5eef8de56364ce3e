"""The benchmark's own library: loading cells by name, traffic, weights,
the trace reduction, shape functions and peaks, and the check that decides
``correct``. Nothing here is imported by the program under test."""
