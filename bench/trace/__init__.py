"""Profiler-trace reduction and the table of device peaks."""
