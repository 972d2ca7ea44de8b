"""Traffic generators, one module per workload family.  A configuration
names its family (``"workload"``); the harness imports
``workloads.<family>`` and calls its ``generate``, ``hot_keys`` and
``initial_values``."""
