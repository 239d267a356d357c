"""Traffic kind `open_loop`: see `serving.py`."""
from .serving import run  # noqa: F401
