"""Traffic loops, named by a traffic mix's ``loop`` key.  Each module
exposes ``setup(run)``, ``window(run)`` and ``check(run)``."""
