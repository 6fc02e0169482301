"""Reading the gateway's `/metrics` text (Prometheus exposition)."""

from __future__ import annotations

import re
from typing import Optional


def gauge(text: Optional[str], name: str) -> Optional[float]:
    """The value of the un-labelled sample whose name ends in `name`."""
    if not text:
        return None
    m = re.search(rf"^\w*{re.escape(name)} ([-+0-9.eE]+)$", text, re.MULTILINE)
    return float(m.group(1)) if m else None
