"""Small shared helpers (no package-internal imports at module load)."""

import functools
import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def cache_dir(*parts: str) -> str:
    """``<checkout>/.cache`` joined with ``parts`` — the one home of
    everything the program builds for itself at run time: the two
    native libraries (keyed by source hash) and the default XLA
    persistent compile cache. Inside the checkout (git-ignored) so two
    checkouts measured in turn on one machine share no state."""
    return os.path.join(_CHECKOUT, ".cache", *parts)


@functools.lru_cache(maxsize=1)
def machine_tag() -> str:
    """Short hash of this machine's CPU architecture and feature flags.
    The native libraries are built ``-march=native``; a checkout (and
    the ``.cache`` inside it) can be copied to a machine with another
    CPU, where such a binary dies with SIGILL, so their cached file
    names carry this tag next to the source hash."""
    import hashlib
    import platform
    flags = ""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    flags = line
                    break
    except OSError:
        pass
    return hashlib.sha256(
        (platform.machine() + flags).encode()).hexdigest()[:8]
