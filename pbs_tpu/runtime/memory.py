"""Device-memory accounting: per-job HBM budgets (page_alloc analog).

Reference: Xen's memory management (``xen/common/page_alloc.c``,
``arch/x86/mm.c``) accounts every page to a domain: ``max_pages`` caps
a domain, ``tot_pages`` tracks usage, domain builds *claim* pages up
front so admission fails fast instead of OOMing mid-boot, and the
balloon driver (``drivers/xen/balloon.c``) reclaims guest memory
cooperatively under pressure.

TPU re-expression: HBM is the contended resource. A
:class:`MemoryManager` owns one device's capacity; jobs open accounts
with optional caps, *claim* their working-set bytes at admission
(fail-fast, the claim mechanism), and can register balloon callbacks
the manager invokes under pressure (e.g. drop optimizer-state
rematerialization caches, shrink activation checkpoints). Real usage
on hardware comes from ``jax.Device.memory_stats()``; estimates for
jitted jobs come from the pytree byte size of their state.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

from pbs_tpu.obs.lockprof import ProfiledLock
from pbs_tpu.obs.perfc import perfc


class OutOfDeviceMemory(MemoryError):
    """Admission-time claim failure (the -ENOMEM a domain build gets
    when its claim exceeds free heap). ``reason`` is ``"cap"`` (per-
    account limit — ballooning others cannot help) or ``"capacity"``
    (device pressure — reclaim may free room)."""

    def __init__(self, msg: str, reason: str = "capacity"):
        super().__init__(msg)
        self.reason = reason


def nbytes_of(tree: Any) -> int:
    """Pytree device-byte estimate (arrays only; None/scalars free)."""
    try:
        import jax

        leaves = jax.tree_util.tree_leaves(tree)
    except Exception:
        leaves = [tree] if tree is not None else []
    total = 0
    for leaf in leaves:
        nb = getattr(leaf, "nbytes", None)
        if nb is not None:
            total += int(nb)
    return total


def device_memory_stats(device=None) -> dict:
    """Live HBM numbers from the runtime (bytes_in_use / bytes_limit)
    for ``device`` (default: the first JAX device), empty when the
    backend doesn't expose them (the CPU returns None)."""
    import jax

    dev = device if device is not None else jax.devices()[0]
    return dict(dev.memory_stats() or {})


@dataclasses.dataclass
class MemoryAccount:
    """Per-domain accounting record (``struct domain``'s max_pages /
    tot_pages pair, in bytes)."""

    owner: str
    max_bytes: int = 0  # 0 = uncapped (dom0-style)
    used_bytes: int = 0
    claims: int = 0  # successful claim count (perfc-style)


class MemoryManager:
    """One device's HBM ledger: capacity, accounts, claims, ballooning."""

    def __init__(self, capacity_bytes: int, reserve_bytes: int = 0):
        # reserve = the runtime's own arena (Xen keeps a hypervisor
        # heap reserve the same way).
        self.capacity = int(capacity_bytes)
        self.reserve = int(reserve_bytes)
        self._accounts: dict[str, MemoryAccount] = {}
        self._reclaim: dict[str, Callable[[int], int]] = {}
        self._lock = ProfiledLock("memory_manager")

    @classmethod
    def for_device(cls, device=None,
                   default_capacity: int | None = None) -> "MemoryManager":
        """Capacity from the runtime's own ``bytes_limit``; where the
        backend reports none, ``default_capacity`` if given, else the
        device's entry in the peak table (telemetry/peaks.py — an
        unlisted TPU kind raises rather than being taken for 16 GiB)."""
        stats = device_memory_stats(device)
        if "bytes_limit" in stats:
            cap = int(stats["bytes_limit"])
        elif default_capacity is not None:
            cap = int(default_capacity)
        else:
            from pbs_tpu.telemetry.peaks import device_peaks

            cap = device_peaks(device).hbm_bytes
        used = int(stats.get("bytes_in_use", 0))
        return cls(cap, reserve_bytes=used)

    # -- accounts --------------------------------------------------------

    def open_account(self, owner: str, max_bytes: int = 0) -> MemoryAccount:
        with self._lock:
            if owner in self._accounts:
                raise ValueError(f"account {owner!r} exists")
            acct = MemoryAccount(owner, max_bytes=int(max_bytes))
            self._accounts[owner] = acct
            return acct

    def close_account(self, owner: str) -> int:
        """Returns the bytes freed (domain destruction releases all)."""
        with self._lock:
            acct = self._accounts.pop(owner, None)
            self._reclaim.pop(owner, None)
            return acct.used_bytes if acct else 0

    def account(self, owner: str) -> MemoryAccount:
        with self._lock:
            return self._accounts[owner]

    # -- claims (fail-fast admission) ------------------------------------

    def free_bytes(self) -> int:
        with self._lock:
            return self._free_locked()

    def _free_locked(self) -> int:
        used = sum(a.used_bytes for a in self._accounts.values())
        return self.capacity - self.reserve - used

    def claim(self, owner: str, nbytes: int) -> None:
        """XENMEM_claim_pages: reserve before allocating. Raises
        :class:`OutOfDeviceMemory` on cap or capacity violation —
        admission fails fast rather than OOMing mid-step."""
        nbytes = int(nbytes)
        if nbytes < 0:
            raise ValueError("negative claim")
        with self._lock:
            acct = self._accounts[owner]
            if acct.max_bytes and acct.used_bytes + nbytes > acct.max_bytes:
                perfc.incr("mem_claim_cap_denied")
                raise OutOfDeviceMemory(
                    f"{owner}: claim {nbytes} exceeds cap "
                    f"{acct.max_bytes} (used {acct.used_bytes})",
                    reason="cap")
            if nbytes > self._free_locked():
                perfc.incr("mem_claim_capacity_denied")
                raise OutOfDeviceMemory(
                    f"{owner}: claim {nbytes} exceeds free "
                    f"{self._free_locked()} of {self.capacity}")
            acct.used_bytes += nbytes
            acct.claims += 1
            perfc.incr("mem_claims")

    def release(self, owner: str, nbytes: int) -> int:
        """Returns the bytes actually deducted (the account clamps at
        zero — callers re-claiming later must re-claim THIS amount,
        not their request, or the ledger inflates)."""
        with self._lock:
            acct = self._accounts[owner]
            deducted = min(acct.used_bytes, max(0, int(nbytes)))
            acct.used_bytes -= deducted
            return deducted

    # -- ballooning (cooperative reclaim) --------------------------------

    def register_reclaim(self, owner: str,
                         fn: Callable[[int], int]) -> None:
        """``fn(nbytes) -> freed`` — the balloon driver's target-set
        callback; the job frees caches and reports how much."""
        self._reclaim[owner] = fn

    def balloon(self, want_bytes: int) -> int:
        """Reclaim until ``want_bytes`` are free (or callbacks are
        exhausted). Returns bytes actually freed. Biggest consumers
        first, like the balloon targeting policy.

        A callback that frees nothing — or whose reported freeing does
        not actually grow free capacity — is skipped for the REST OF
        THIS CALL only, never unregistered ("nothing to give right
        now" is transient). A callback that DID free stays eligible,
        so chunked reclaimers (a cache evicting 100 MB per ask) are
        re-asked until the target is met or they dry up."""
        freed_total = 0
        skip: set[str] = set()
        while self.free_bytes() < want_bytes:
            with self._lock:
                candidates = sorted(
                    (a for a in self._accounts.values()
                     if a.owner in self._reclaim and a.used_bytes > 0
                     and a.owner not in skip),
                    key=lambda a: -a.used_bytes)
            if not candidates:
                break
            acct = candidates[0]
            need = want_bytes - self.free_bytes()
            fn = self._reclaim.get(acct.owner)
            if fn is None:  # concurrently unregistered
                skip.add(acct.owner)
                continue
            free_before = self.free_bytes()
            freed = int(fn(need))
            if freed > 0:
                deducted = self.release(acct.owner, freed)
                freed_total += deducted
                perfc.incr("mem_balloon_freed_bytes", deducted)
            if freed <= 0 or self.free_bytes() <= free_before:
                # dry, uncooperative, or claims bytes the ledger never
                # charged it for — either way, asking again this call
                # cannot make progress
                skip.add(acct.owner)
        return freed_total

    def claim_or_balloon(self, owner: str, nbytes: int) -> None:
        """Claim; on capacity pressure, balloon others then retry once.
        A per-account cap denial re-raises immediately — evicting other
        tenants' caches cannot make an over-cap claim succeed."""
        try:
            self.claim(owner, nbytes)
        except OutOfDeviceMemory as e:
            if e.reason == "cap":
                raise
            self.balloon(nbytes)
            self.claim(owner, nbytes)

    # -- observability ---------------------------------------------------

    def dump(self) -> dict:
        with self._lock:
            return {
                "capacity": self.capacity,
                "reserve": self.reserve,
                "free": self._free_locked(),
                "accounts": {
                    a.owner: {"used": a.used_bytes, "max": a.max_bytes,
                              "claims": a.claims}
                    for a in self._accounts.values()
                },
            }
