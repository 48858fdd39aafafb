"""Which device this process computes on — asked once, said out loud.

Every entry point that runs the engine tick calls :func:`claim_device`
before its first jit: it places the persistent compile cache
(utils/jaxcache.py) and brings the backend up.  A named platform is a
pin that fails when JAX cannot bring that backend up; no name means
whatever JAX itself selects (``JAX_PLATFORMS``, else its own probe of
the machine).  Either way the caller gets — and prints — what actually
came up, so a run on the CPU can never pass for a run on the chip.

A chip belongs to one process: a second process that claims an
occupied chip fails here, with libtpu's reason in the message.
"""

from __future__ import annotations

import os
from typing import Any, Dict

__all__ = ["claim_device", "device_line"]


def claim_device(platform: str = "") -> Dict[str, Any]:
    """Place the compile cache and initialise the JAX backend, pinned
    to ``platform`` when one is named.  Returns ``{"platform", "kind",
    "count"}`` as jax reports them; raises ``RuntimeError`` with a
    one-line reason when the backend does not come up or is not the
    one asked for."""
    import jax

    from .jaxcache import enable_compile_cache

    enable_compile_cache()
    if platform:
        jax.config.update("jax_platforms", platform)
    asked = platform or os.environ.get("JAX_PLATFORMS") or "(jax's choice)"
    try:
        devs = jax.devices()
    except RuntimeError as exc:
        reason = " ".join(str(exc).split())
        if "libtpu_lockfile" in reason:
            # What libtpu 0.0.34 says when the chip is taken (measured:
            # the second claimant fails in ~3 s; it does not hang).
            reason += (
                " — another process on this machine holds the chip, and "
                "a chip belongs to one process at a time"
            )
        raise RuntimeError(
            f"JAX could not initialise platform {asked}: {reason}"
        ) from exc
    got = {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }
    if platform and got["platform"] != platform:
        raise RuntimeError(
            f"asked for platform {platform}, JAX came up on "
            f"{got['platform']}"
        )
    return got


def device_line(dev: Dict[str, Any]) -> str:
    """The one line every entry point prints about its device."""
    return (
        f"platform={dev['platform']} device_kind={dev['kind']} "
        f"devices={dev['count']}"
    )
