"""Persistent compiled-program cache (``MXTPU_COMPILE_CACHE_DIR``).

One file per program, named by the key digest::

    <dir>/<sha256-digest>.mxprog

Entry layout (self-describing, CRC-guarded)::

    b"MXPROG1\\n"                     magic
    uint32 big-endian header length
    header JSON   {version, digest, name, kind, fingerprint, crc32,
                   payload_len, created, backend}
    payload bytes (pickled (serialized_executable, in_tree, out_tree,
                   device ids the program was compiled for))

Every write is atomic (``base.atomic_write``: temp + fsync + rename), so
a process killed at any byte never tears an existing entry. On read the
entry is rejected — loudly, with a warning and a counter, never with a
wrong program — when the magic/header don't parse (``corrupt``), the
payload CRC32 or length disagree with the header (``corrupt``: bit rot,
truncation, torn storage below the rename), or the stored version
fingerprint differs from the running stack (``stale``: a jax / jaxlib /
mxnet_tpu upgrade). A rejected entry is overwritten in place by the
fresh compile that replaces it.

Fault injection: the ``compile_cache`` site covers both failure shapes —
``compile_cache:byte=N[:action=kill]`` arms a byte-budgeted write fault
(via the :func:`base.atomic_write` ``guarded_write`` hook), and
``compile_cache:bytes=N`` truncates the entry AFTER the rename commits
(storage lying below the rename), which the CRC must catch on load.
"""
from __future__ import annotations

import json
import os
import struct
import time
import zlib

from ..base import MXNetError, atomic_write

__all__ = ["PersistentCache", "CacheEntryError", "default_cache",
           "cache_enabled", "wire_jax_cache", "JAX_CACHE_DIR"]

_MAGIC = b"MXPROG1\n"
_SUFFIX = ".mxprog"


class CacheEntryError(MXNetError):
    """A cache entry exists but must not be used. ``reason`` is
    ``"corrupt"`` (magic/CRC/length mismatch) or ``"stale"`` (version
    fingerprint mismatch)."""

    def __init__(self, path, reason, detail=""):
        super().__init__(
            f"compile-cache entry '{os.path.basename(path)}' is {reason}"
            f"{': ' + detail if detail else ''}; falling back to a fresh "
            "compile (the entry will be overwritten)")
        self.path = path
        self.reason = reason


class PersistentCache:
    """See module docstring. Construct with an explicit directory, or
    use :func:`default_cache` for the ``MXTPU_COMPILE_CACHE_DIR`` one."""

    def __init__(self, directory):
        self.directory = os.fspath(directory)

    @property
    def enabled(self):
        return bool(self.directory)

    def path_for(self, digest):
        return os.path.join(self.directory, digest + _SUFFIX)

    # -- write ----------------------------------------------------------------
    def put(self, key, payload, fingerprint=None):
        """Atomically write one entry. ``payload`` is the pickled
        serialized-executable blob; ``key`` a ProgramKey. Returns the
        entry path. ``fingerprint`` is overridable for tests only."""
        from . import key as key_mod
        from .. import faultinject
        os.makedirs(self.directory, exist_ok=True)
        header = {
            "version": key_mod.FORMAT_VERSION,
            "digest": key.digest,
            "name": key.name,
            "kind": key.kind,
            "fingerprint": fingerprint or key_mod.fingerprint(),
            "crc32": zlib.crc32(payload) & 0xFFFFFFFF,
            "payload_len": len(payload),
            "created": time.time(),
            "backend": key.materials.get("backend"),
        }
        hdr = json.dumps(header, sort_keys=True).encode("utf-8")
        path = self.path_for(key.digest)
        # the byte-budget fault site rides atomic_write's guarded_write
        # hook, which arms on the 'ckpt_write' site by default — consult
        # the compile_cache site here and re-arm the generic hook
        with atomic_write(path) as f:
            f = faultinject.guarded_write(f, path=path,
                                          site="compile_cache")
            f.write(_MAGIC)
            f.write(struct.pack(">I", len(hdr)))
            f.write(hdr)
            f.write(payload)
        # post-commit tearing (lying storage below the rename): the CRC
        # recorded in the header is what must catch it on load
        faultinject.maybe_truncate(path, site="compile_cache")
        return path

    # -- read -----------------------------------------------------------------
    def read_header(self, path):
        """Parse one entry's header; raises CacheEntryError("corrupt")
        when the magic/header don't parse."""
        try:
            with open(path, "rb") as f:
                if f.read(len(_MAGIC)) != _MAGIC:
                    raise CacheEntryError(path, "corrupt", "bad magic")
                (hlen,) = struct.unpack(">I", f.read(4))
                if hlen <= 0 or hlen > (1 << 20):
                    raise CacheEntryError(path, "corrupt",
                                          "implausible header length")
                return json.loads(f.read(hlen).decode("utf-8"))
        except CacheEntryError:
            raise
        except (OSError, ValueError, struct.error,
                UnicodeDecodeError) as e:
            raise CacheEntryError(path, "corrupt", str(e))

    def get(self, digest):
        """Return the payload bytes for ``digest`` after full
        validation, or None when there is no entry. Raises
        :class:`CacheEntryError` on a corrupt or version-stale entry —
        the caller falls back to a fresh compile and overwrites.

        One open, one sequential read: a concurrent overwrite of the
        entry (shared cache volume; atomic_write renames a fresh file
        into place) can never mix the old header with the new payload.
        """
        from . import key as key_mod
        path = self.path_for(digest)
        try:
            with open(path, "rb") as f:
                if f.read(len(_MAGIC)) != _MAGIC:
                    raise CacheEntryError(path, "corrupt", "bad magic")
                (hlen,) = struct.unpack(">I", f.read(4))
                if hlen <= 0 or hlen > (1 << 20):
                    raise CacheEntryError(path, "corrupt",
                                          "implausible header length")
                header = json.loads(f.read(hlen).decode("utf-8"))
                payload = f.read()
        except FileNotFoundError:
            return None
        except CacheEntryError:
            raise
        except (OSError, ValueError, struct.error,
                UnicodeDecodeError) as e:
            raise CacheEntryError(path, "corrupt", str(e))
        if header.get("fingerprint") != key_mod.fingerprint():
            raise CacheEntryError(
                path, "stale",
                f"built by {header.get('fingerprint')!r}, running "
                f"{key_mod.fingerprint()!r}")
        if len(payload) != header.get("payload_len") or \
                (zlib.crc32(payload) & 0xFFFFFFFF) != header.get("crc32"):
            raise CacheEntryError(
                path, "corrupt",
                f"payload CRC/length mismatch ({len(payload)} bytes)")
        return payload

    # -- maintenance (tools/compile_cache.py) ---------------------------------
    def entries(self):
        """[(path, header-or-CacheEntryError)] for every entry file,
        newest first."""
        if not os.path.isdir(self.directory):
            return []
        out = []
        for name in os.listdir(self.directory):
            if not name.endswith(_SUFFIX):
                continue
            path = os.path.join(self.directory, name)
            try:
                out.append((path, self.read_header(path)))
            except CacheEntryError as e:
                out.append((path, e))
        out.sort(key=lambda pe: -os.path.getmtime(pe[0]))
        return out

    def verify(self):
        """Fully validate every entry (header + fingerprint + CRC).
        Returns (ok_count, [(path, reason), ...] for the bad ones)."""
        ok, bad = 0, []
        for path, header in self.entries():
            if isinstance(header, CacheEntryError):
                bad.append((path, header.reason))
                continue
            try:
                self.get(header["digest"])
                ok += 1
            except CacheEntryError as e:
                bad.append((path, e.reason))
        return ok, bad

    def prune(self, max_age_s=None, max_bytes=None, remove_invalid=True):
        """Retention: drop entries older than ``max_age_s``, then drop
        oldest-first until total size fits ``max_bytes``; invalid
        entries always go first. Returns [(path, why)] removed."""
        removed = []
        entries = self.entries()
        now = time.time()
        live = []
        for path, header in entries:
            if isinstance(header, CacheEntryError):
                if remove_invalid:
                    removed.append((path, header.reason))
                    continue
                header = {}
            age = now - float(header.get("created") or
                              os.path.getmtime(path))
            if max_age_s is not None and max_age_s > 0 and age > max_age_s:
                removed.append((path, f"age {age / 86400.0:.1f}d"))
                continue
            live.append((path, os.path.getsize(path)))
        if max_bytes is not None and max_bytes > 0:
            total = sum(s for _, s in live)
            # live is newest-first: evict from the tail (oldest)
            while total > max_bytes and live:
                path, size = live.pop()
                total -= size
                removed.append((path, "size budget"))
        for path, _why in removed:
            try:
                os.unlink(path)
            except OSError:
                pass
        return removed


# where JAX's own persistent compilation cache lives when nobody placed
# it from outside: a FIXED path inside the checkout. The directory is
# part of JAX's cache key, so a temporary name, a pid or a timestamp in
# it would make every run a miss.
JAX_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def wire_jax_cache():
    """Place JAX's persistent compilation cache; returns the directory.
    THE one place the program configures it, called once when the
    package is imported — before the first compile, because JAX decides
    once per process whether its cache is in use. If
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set in code; otherwise the cache goes to
    :data:`JAX_CACHE_DIR`. This layer is independent of the ``.mxprog``
    AOT entries under ``MXTPU_COMPILE_CACHE_DIR``, which are keyed by
    digest and may live anywhere."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax
    jax.config.update("jax_compilation_cache_dir", JAX_CACHE_DIR)
    return JAX_CACHE_DIR


def cache_enabled():
    """Resolve MXTPU_COMPILE_CACHE / MXTPU_COMPILE_CACHE_DIR: on when a
    directory is configured and the switch isn't 0/off."""
    from .. import config
    if not str(config.get("MXTPU_COMPILE_CACHE_DIR") or ""):
        return False
    return str(config.get("MXTPU_COMPILE_CACHE")).lower() not in \
        ("0", "false", "off")


def default_cache():
    """The env-configured cache, or None when disabled."""
    from .. import config
    if not cache_enabled():
        return None
    return PersistentCache(str(config.get("MXTPU_COMPILE_CACHE_DIR")))
