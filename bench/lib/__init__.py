"""The benchmark's yardstick: discovery of cells, metrics and drivers
by name, the compile counters, the peak table, the MAC count, the
arrival schedule and the reduction of a profiler trace."""
