"""Traffic mixes (``<mix>.json``, data only) and the drivers that read them
(``<kind>.py``, one per ``kind``); see ``bench/harness.py``."""
