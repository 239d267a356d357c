"""Traffic kind `closed_loop`: see `serving.py`."""
from .serving import run  # noqa: F401
