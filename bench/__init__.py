"""The benchmark of the device search path: ``python3 -m bench.run``."""
