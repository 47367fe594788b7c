"""Device: 1 minus the union of the device-op intervals over the traced
window, mean over the chips used."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _trace import idle_pct as read  # noqa: E402,F401
