"""The benchmark's yardstick: discovery, traffic runners, trace reduction, checks."""
