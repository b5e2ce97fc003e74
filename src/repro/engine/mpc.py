"""MPCEngine — share-level interpretation of the proxy forward.

Tensors are `Share`s over a `RingSpec` and a protocol backend
(`mpc/protocols/`): RING64 and RING32 share this one code path, and so
do the additive-2PC (trusted-dealer Beaver) and replicated-3PC
(dealer-free resharing) protocols — the ring decides the truncation
arithmetic, the backend decides the sharing scheme and what lands in
the cost Ledger. All six variant strategies run bitwise-reproducibly on
every (ring, protocol) combination: the op stream is fixed by
`engine/forward.py` and keys derive deterministically below.

Fixed-point scale flows through the forward as share metadata
(`Share.fb`, mpc/scale.py): products ride at 2f, pow2 rescales fold
free, and forced truncations fire only where the lattice demands —
the engine adds exactly one boundary rule of its own, `entropy_head`
returns CANONICAL-scale scores (QuickSelect and appraisal consume
them as public-contract fb == frac_bits).

PRNG keys are threaded internally: the engine is seeded once per
forward (`with_key`) and derives one key per keyed op site by folding
an op counter.  The op sequence is fixed by `engine/forward.py`, so the
derived key stream is deterministic — the wave executor's schedule
variants (vmapped wave vs per-lane serial) see identical keys and
therefore produce bitwise-identical shares.

Exact-op variant strategies (softmax / rsqrt / entropy when the MLP
emulator is ablated, plus the 2Quad and polynomial baseline softmaxes)
run the real CrypTen-style protocols from `mpc/nonlinear.py` — this is
what lets Table 3's baselines be *executed* over MPC, not only priced.
"""
import jax
import jax.numpy as jnp

from repro.engine.forward import _mlp_at
from repro.mpc import compare, fusion, nonlinear, protocols, ops as mops
from repro.mpc.ring import RING64, RingSpec
from repro.mpc.sharing import Share
from repro.spans import span


def _ax(axis: int) -> int:
    """Value axis -> share-array axis (leading party axis)."""
    return axis + 1 if axis >= 0 else axis


def mlp_apply_mpc(p_sh: dict, x: Share, key) -> Share:
    """Share-level emulator MLP: weights are model-owner-private shares.

    Cost: 2 secure matmuls (1 round each) + ReLU over `hidden` elements
    only — the dimension reduction the paper's MPC savings come from.
    Canonical home of the share-level apply path; the clear twin lives
    in engine/clear.mlp_apply.
    """
    def _badd(h: Share, b: Share) -> Share:
        # build the broadcast from b (it carries b's exponent — h may
        # ride at 2f, and add() lifts the bias to match, exactly)
        bb = b.with_sh(jnp.broadcast_to(b.sh[:, None, :], h.sh.shape))
        return mops.add(h, bb)

    k1, k2, k3 = jax.random.split(key, 3)
    h = mops.matmul(x, p_sh["w1"], k1)
    h = _badd(h, p_sh["b1"])
    h = compare.relu(h, k2)
    out = mops.matmul(h, p_sh["w2"], k3)
    return _badd(out, p_sh["b2"])


class MPCEngine:
    kind = "mpc"

    def __init__(self, ring: RingSpec = RING64, variant=None, key=None,
                 combine_impl: str = "auto", protocol: str = "2pc"):
        self.ring = ring
        self.variant = variant
        self._key = key
        self._ctr = 0
        # protocol backend: "2pc" (additive + trusted dealer), "3pc"
        # (replicated 2-of-3, dealer-free), "spdz2pc" (malicious, MAC'd)
        # or "aby3trunc" (3pc + exact trunc2) — mpc/protocols/
        self.protocol = protocol
        self.backend = protocols.get(protocol)
        # Beaver post-open combine for 2-D RING32 2PC matmuls: the fused
        # Pallas secure_matmul kernel ("auto" = compiled on TPU, jnp
        # reference elsewhere; "interpret" exercises the kernel body on
        # CPU). Bitwise-identical wrapping int32 arithmetic either way.
        self.combine_impl = combine_impl

    def with_key(self, key) -> "MPCEngine":
        """Fresh engine seeded for one forward (keys derive from here)."""
        return MPCEngine(self.ring, self.variant, key=key,
                         combine_impl=self.combine_impl,
                         protocol=self.protocol)

    def fused(self, label: str):
        """Mark a group of independent ops: their openings/reshares ride
        one flight under an ambient `fusion.flight_scope` (no-op
        eagerly)."""
        return fusion.fused_group(label)

    def _k(self):
        if self._key is None:
            raise ValueError("MPCEngine needs a PRNG seed: call "
                             "engine.with_key(key) before the forward")
        with span("mpc.key"):
            k = jax.random.fold_in(self._key, self._ctr)
        self._ctr += 1
        return k

    # -- data entry ------------------------------------------------------
    def embed(self, pp, x_in, cfg):
        if not isinstance(x_in, Share):
            raise TypeError(
                "MPCEngine consumes shared embedded inputs (B, S, d): the "
                "data owner shares one-hot rows and the embedding matmul "
                "is folded into share generation (see mpc/sharing.share)")
        if x_in.proto != self.protocol:
            raise ValueError(
                f"engine protocol {self.protocol!r} but input shares are "
                f"{x_in.proto!r} — share the inputs with "
                f"share(..., proto={self.protocol!r})")
        return x_in

    # -- linear algebra --------------------------------------------------
    # add/sub get a key: exponent alignment is usually an exact lift,
    # but a pow2-folded operand above the 2f cap (layer>=2 mean vs the
    # 2f residual) must down-trunc EXACTLY — keyless local shifts wrap
    # too often at fb > 2f on the 32-bit ring
    def add(self, x, y):
        return mops.add(x, y, key=self._k())

    def sub(self, x, y):
        return mops.sub(x, y, key=self._k())

    def mul(self, x, y):
        return mops.mul(x, y, self._k())

    def mul_public(self, x, v):
        return mops.mul_public(x, v, key=self._k())

    def add_public(self, x, v):
        return mops.add_public(x, v)

    def matmul(self, x, y):
        combine = self.combine_impl \
            if self.ring.bits == 32 and self.protocol == "2pc" else None
        return mops.matmul(x, y, self._k(), combine_impl=combine)

    def mean(self, x, axis):
        return mops.mean(x, axis=axis, key=self._k())

    # -- shape ops (local on shares) -------------------------------------
    # Layout ops go through Share.derive: they are scale-preserving AND
    # remember their source, so a forced truncation on (say) a broadcast
    # inverse-std fires on the small pre-broadcast tensor and the free
    # layout replays — fewer dealer trunc-pair bytes for the same event.
    def shape(self, x):
        return x.shape

    def reshape(self, x, shape):
        return x.reshape(*shape)

    def broadcast(self, x, shape):
        # right-align the VALUE dims under the leading party axis: a
        # (P, n)-share broadcast to value shape (rows, n) must become
        # (P, 1, n) first, or the party axis would be matched against a
        # value dim (the attention-bias path hits exactly this)
        shape = tuple(shape)
        pad = len(shape) - x.ndim
        val_shape = x.shape

        def fn(sh):
            sh = sh.reshape((sh.shape[0],) + (1,) * pad + val_shape)
            return jnp.broadcast_to(sh, (sh.shape[0],) + shape)

        return x.derive(fn)

    def moveaxis(self, x, src, dst):
        return x.derive(lambda sh: jnp.moveaxis(sh, _ax(src), _ax(dst)))

    def swapaxes(self, x, a, b):
        return x.derive(lambda sh: jnp.swapaxes(sh, _ax(a), _ax(b)))

    def index(self, x, i):
        # no lineage: forcing a slice must not truncate the whole source
        return x.with_sh(x.sh[:, i])

    # -- nonlinearity strategies -----------------------------------------
    def mlp(self, p, x):
        return mlp_apply_mpc(p, x, self._k())

    def ln_inv(self, pp, li, var, variant):
        if "ln" in variant:
            return self.mlp(_mlp_at(pp["mlp_ln"], li), var)
        return nonlinear.rsqrt(mops.add_public(var, 1e-5), self._k())

    def attn_probs(self, pp, li, scores, variant):
        if "sm" in variant:
            return self.mlp(_mlp_at(pp["mlp_sm"], li), scores)
        if "quad_sm" in variant:
            return self._quad_softmax(scores)
        if "poly_sm" in variant:
            return self._poly_softmax(scores)
        return nonlinear.softmax(scores, self._k(), axis=-1)

    def entropy_head(self, pp, logits, variant):
        """Entropy scores, forced to CANONICAL scale: the forward's
        public boundary. Downstream consumers (QuickSelect ranking,
        appraisal means, decode-at-f callers) see fb == frac_bits."""
        b = logits.shape[0]
        if "se" in variant:
            out = self.mlp(pp["mlp_se"], logits).reshape(b)
        else:
            out = nonlinear.entropy_from_logits(logits, self._k())
        out = mops.force(out, self._k())
        # Malicious backends (spdz2pc) verify every partial opening of
        # the forward with ONE batched MAC check at this boundary — the
        # constant-size flight that makes the whole forward abort on
        # tamper. Semi-honest backends have no hook; nothing fires.
        check = getattr(self.backend, "mac_check_flight", None)
        if check is not None:
            check(self.ring)
        return out

    # -- Table-3 baseline softmaxes over shares --------------------------
    def _quad_softmax(self, scores):
        """MPCFormer 2Quad: (x+5)^2 / sum — square + NR reciprocal."""
        a = mops.add_public(scores, 5.0)
        e = mops.mul(a, a, self._k())
        s = mops.sum_(e, axis=-1, keepdims=True)
        # clamp mirroring the clear strategy's max(sum, 1e-6): keeps the
        # NR reciprocal away from a near-zero pole when every score in a
        # row sits near -5
        s = mops.add_public(s, 1e-6)
        r = nonlinear.reciprocal(s, self._k())
        rb = r.with_sh(jnp.broadcast_to(r.sh, e.sh.shape))
        return mops.mul(e, rb, self._k())

    def _poly_softmax(self, scores):
        """Bolt-style polynomial exp of clipped, max-shifted scores.

        clip(t, -8, 0) over shares: max(t,-8) = relu(t+8)-8, then
        min(u,0) = u - relu(u) — two comparisons per element, matching
        the baseline's real MPC cost profile. The comparisons are
        scale-invariant and their bits multiply at exponent 0, so the
        whole clip chain rides at the scores' carried exponent without
        a single truncation.
        """
        mx = compare.max_(scores, axis=-1, key=self._k())
        mb = mx.with_sh(jnp.broadcast_to(mx.sh, scores.sh.shape))
        # keyed subs: the max-shift may align carried exponents DOWN a
        # real truncation (keyless degrades to the local-shift path —
        # absent for MAC'd shares, wrap-prone on RING32)
        t = mops.sub(scores, mb, key=self._k())
        lo = mops.add_public(compare.relu(mops.add_public(t, 8.0), self._k()),
                             -8.0)
        t = mops.sub(lo, compare.relu(lo, self._k()), key=self._k())
        # Horner: e = 1 + t(1 + t(1/2 + t(1/6 + t/24))) — one fused
        # flight: every message is a mask component, the public parts of
        # the chained openings reconstruct locally (fusion.py legality).
        # Scale carrying does the cross-op trunc folding here: each
        # step's product emits at 2f and the next mul's headroom plan
        # forces exactly one trunc — the PendingShare choreography this
        # chain used to motivate is gone.
        with fusion.fused_group("horner"):
            acc = mops.add_public(mops.mul_public(t, 1.0 / 24.0,
                                                  key=self._k()), 1.0 / 6.0)
            acc = mops.add_public(mops.mul(t, acc, self._k()), 0.5)
            acc = mops.add_public(mops.mul(t, acc, self._k()), 1.0)
            e = mops.add_public(mops.mul(t, acc, self._k()), 1.0)
        e = compare.relu(e, self._k())
        s = mops.sum_(e, axis=-1, keepdims=True)
        r = nonlinear.reciprocal(s, self._k())
        rb = r.with_sh(jnp.broadcast_to(r.sh, e.sh.shape))
        return mops.mul(e, rb, self._k())
