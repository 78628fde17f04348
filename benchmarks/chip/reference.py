"""Plain reference: the trial wavefunction and the two propagators.

Dense all-electron evaluation in plain ``jax.numpy`` float32, every
product at ``Precision.HIGHEST`` unless the caller asks for a lower one:

    chi_j(r_i) and its gradient and Laplacian, for every AO (zero outside
    the atom's screening radius)  ->  C = A @ B  ->  per spin block
    slogdet and inverse of D = C[..., 0]  ->  grad log|D| and lap D / D
    ->  Pade Jastrow  ->  Coulomb potential  ->  E_L

and, on top of it, one sub-block of each propagator replayed from given
walkers and keys: all-electron drift-diffusion Metropolis (VMC), and
sweeps of single-electron Metropolis moves with a determinant ratio from
the inverse and a rank-1 inverse update (SEM-VMC).
The random numbers are drawn from the keys the program's block loop
derives: ``fold_in`` of the generation on the sub-block key, then of the
walker index (and of the electron, for single-electron moves).

It imports nothing of the program.  Everything runs in walker chunks so
that the largest configuration fits beside nothing else on one chip.

``precision`` selects how every matrix product is computed: ``highest``
(float32), ``high`` (three bfloat16 passes: the operands split into a
high and a low bfloat16 part, the low-by-low term dropped) or ``bf16``
(one bfloat16 pass).  The two lower ones are the control that the
comparison in ``compare.py`` has to fail.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
PRECISIONS = ('highest', 'high', 'bf16')


def mm(spec: str, a, b, precision: str = 'highest'):
    """einsum of two float32 operands at the named precision."""
    if precision == 'highest':
        return jnp.einsum(spec, a, b, precision=HI)
    bf = jnp.bfloat16

    def _dot(x, y):
        return jnp.einsum(spec, x, y, preferred_element_type=jnp.float32)

    a_hi, b_hi = a.astype(bf), b.astype(bf)
    if precision == 'bf16':
        return _dot(a_hi, b_hi)
    if precision != 'high':
        raise ValueError(f'unknown precision {precision!r}')
    a_lo = (a - a_hi.astype(jnp.float32)).astype(bf)
    b_lo = (b - b_hi.astype(jnp.float32)).astype(bf)
    return _dot(a_hi, b_hi) + _dot(a_hi, b_lo) + _dot(a_lo, b_hi)


def _pow(x, k):
    """x ** k for an integer array k in [-2, 3]; 0 where k < 0."""
    out = jnp.where(k == 1, x, jnp.where(k == 2, x * x, x * x * x))
    out = jnp.where(k == 0, 1.0, out)
    return jnp.where(k < 0, 0.0, out)


class Reference:
    """Jitted reference functions for one System."""

    def __init__(self, sysd, precision: str = 'highest', chunk: int = 64):
        if precision not in PRECISIONS:
            raise ValueError(f'unknown precision {precision!r}')
        self.s = sysd
        self.precision = precision
        self.chunk = int(chunk)
        self.coords = jnp.asarray(sysd.coords, jnp.float32)
        self.charges = jnp.asarray(sysd.charges, jnp.float32)
        self.mo = jnp.asarray(sysd.mo, jnp.float32)
        self._eval = jax.jit(jax.vmap(self._walker))
        self._vals = jax.jit(self._ao_values)

    # -- one walker ------------------------------------------------------
    def _ao(self, r):
        """(n_ao, n_e, 5): value, gradient, Laplacian of every AO."""
        s = self.s
        R = self.coords[np.asarray(s.ao_atom)]                  # (n_ao, 3)
        d = r[:, None, :] - R[None]                             # (n_e,n_ao,3)
        r2 = jnp.sum(d * d, axis=-1)
        inside = r2 < jnp.asarray(s.atom_radius2)[np.asarray(s.ao_atom)]
        c = jnp.asarray(s.prim_coeff)
        a = jnp.asarray(s.prim_exp)
        e = jnp.exp(-a[None] * r2[..., None])
        g = jnp.sum(c * e, axis=-1)
        g1 = jnp.sum(-a * c * e, axis=-1)                       # dg/d(r^2)
        g2 = jnp.sum(a * a * c * e, axis=-1)
        k = jnp.asarray(s.ao_pow, jnp.int32)                    # (n_ao, 3)
        n = k.astype(jnp.float32)
        f = [_pow(d[..., i], k[:, i]) for i in range(3)]
        df = [n[:, i] * _pow(d[..., i], k[:, i] - 1) for i in range(3)]
        d2f = [n[:, i] * (n[:, i] - 1) * _pow(d[..., i], k[:, i] - 2)
               for i in range(3)]
        poly = f[0] * f[1] * f[2]
        val = poly * g
        grads, lap = [], 0.0
        for i in range(3):
            o = f[(i + 1) % 3] * f[(i + 2) % 3]
            x = d[..., i]
            grads.append(df[i] * o * g + poly * 2.0 * x * g1)
            lap = lap + (d2f[i] * o * g + 4.0 * df[i] * o * x * g1
                         + poly * (2.0 * g1 + 4.0 * x * x * g2))
        B = jnp.stack([val] + grads + [lap], axis=-1)
        B = jnp.where(inside[..., None], B, 0.0)
        return jnp.swapaxes(B, 0, 1)

    def _ao_values(self, pts):
        """AO values only at points (N, 3) -> (N, n_ao)."""
        s = self.s
        R = self.coords[np.asarray(s.ao_atom)]
        d = pts[:, None, :] - R[None]
        r2 = jnp.sum(d * d, axis=-1)
        inside = r2 < jnp.asarray(s.atom_radius2)[np.asarray(s.ao_atom)]
        g = jnp.sum(jnp.asarray(s.prim_coeff)
                    * jnp.exp(-jnp.asarray(s.prim_exp)[None] * r2[..., None]),
                    axis=-1)
        k = jnp.asarray(s.ao_pow, jnp.int32)
        poly = 1.0
        for i in range(3):
            poly = poly * _pow(d[..., i], k[:, i])
        return jnp.where(inside, poly * g, 0.0)

    def _jastrow(self, r):
        b_ee, b_en, a_en = self.s.jastrow
        n_e, n_up = r.shape[0], self.s.n_up
        eye = jnp.eye(n_e, dtype=bool)
        diff = r[:, None, :] - r[None, :, :]
        rij = jnp.sqrt(jnp.where(eye, 1.0, jnp.sum(diff * diff, axis=-1)))
        up = jnp.arange(n_e) < n_up
        a = jnp.where(up[:, None] == up[None, :], 0.25, 0.5)
        den = 1.0 + b_ee * rij
        u, u1, u2 = a * rij / den, a / den ** 2, -2.0 * a * b_ee / den ** 3
        m = 1.0 - eye
        val = 0.5 * jnp.sum(u * m)
        grad = jnp.sum((u1 * m)[..., None] * diff / rij[..., None], axis=1)
        lap = jnp.sum((u2 + 2.0 * u1 / rij) * m, axis=1)
        dn = r[:, None, :] - self.coords[None]
        rn = jnp.sqrt(jnp.sum(dn * dn, axis=-1) + 1e-20)
        an = -self.charges[None] * a_en
        den = 1.0 + b_en * rn
        v, v1, v2 = an * rn / den, an / den ** 2, -2.0 * an * b_en / den ** 3
        val = val + jnp.sum(v)
        grad = grad + jnp.sum(v1[..., None] * dn / rn[..., None], axis=1)
        lap = lap + jnp.sum(v2 + 2.0 * v1 / rn, axis=1)
        return val, grad, lap

    def _potential(self, r):
        n_e = r.shape[0]
        eye = jnp.eye(n_e, dtype=bool)
        dn = r[:, None, :] - self.coords[None]
        v = -jnp.sum(self.charges[None] / jnp.sqrt(jnp.sum(dn * dn, -1)
                                                   + 1e-20))
        de = r[:, None, :] - r[None]
        ree = jnp.sqrt(jnp.sum(de * de, -1) + jnp.where(eye, 1.0, 0.0))
        v = v + 0.5 * jnp.sum(jnp.where(eye, 0.0, 1.0 / ree))
        dc = self.coords[:, None] - self.coords[None]
        n_a = self.coords.shape[0]
        ea = jnp.eye(n_a, dtype=bool)
        rc = jnp.sqrt(jnp.sum(dc * dc, -1) + jnp.where(ea, 1.0, 0.0))
        zz = self.charges[:, None] * self.charges[None]
        return v + 0.5 * jnp.sum(jnp.where(ea, 0.0, zz / rc))

    def _spin(self, Cb):
        """One spin block (orb, elec, 5) -> sign, log|D|, grad, lap, D^-1."""
        D = Cb[..., 0]
        with jax.default_matmul_precision('highest'):
            sign, logdet = jnp.linalg.slogdet(D)
            M = jnp.linalg.inv(D)                              # (elec, orb)
        grad = mm('iec,ei->ec', Cb[..., 1:4], M, self.precision)
        lap = mm('ie,ei->e', Cb[..., 4], M, self.precision)
        return sign, logdet, grad, lap, M

    def _walker(self, r):
        s = self.s
        B = self._ao(r)
        C = mm('oa,aef->oef', self.mo, B, self.precision)     # (orb, n_e, 5)
        su, lu, gu, qu, mu = self._spin(C[:s.n_up, :s.n_up])
        sd, ld, gd, qd, md = self._spin(C[:s.n_dn, s.n_up:])
        sgrad = jnp.concatenate([gu, gd])
        slap = jnp.concatenate([qu, qd])
        jv, jg, jl = self._jastrow(r)
        lap = (slap + jl + jnp.sum(jg * jg, -1)
               + 2.0 * jnp.sum(jg * sgrad, -1))
        e_loc = -0.5 * jnp.sum(lap) + self._potential(r)
        return dict(sign=su * sd, log_psi=lu + ld + jv, drift=sgrad + jg,
                    e_loc=e_loc, minv_up=mu, minv_dn=md)

    # -- ensembles -------------------------------------------------------
    def evaluate(self, R):
        """R: (W, n_e, 3) -> dict of device arrays, one row per walker."""
        R = jnp.asarray(R, jnp.float32)
        parts = [self._eval(R[i:i + self.chunk])
                 for i in range(0, R.shape[0], self.chunk)]
        return {k: jnp.concatenate([p[k] for p in parts])
                for k in parts[0]}

    # -- propagators -----------------------------------------------------
    @staticmethod
    def _walker_draws(key, W, shape):
        """Per-walker (normal of ``shape``, uniform) from fold_in(key, w)."""
        def one(i):
            ke, ku = jax.random.split(jax.random.fold_in(key, i))
            return (jax.random.normal(ke, shape, jnp.float32),
                    jax.random.uniform(ku, (), jnp.float32))
        return jax.vmap(one)(jnp.arange(W))

    def _diffusion(self, ens, key, tau):
        R = ens['r']
        W = R.shape[0]
        eta, u = self._walker_draws(key, W, R.shape[1:])
        Rn = R + tau * ens['drift'] + jnp.sqrt(tau) * eta
        new = dict(self.evaluate(Rn), r=Rn)

        def log_g(to, frm, drift):
            d = to - frm - tau * drift
            return -jnp.sum(d * d, axis=(-1, -2)) / (2.0 * tau)

        log_ratio = (2.0 * (new['log_psi'] - ens['log_psi'])
                     + log_g(R, Rn, new['drift'])
                     - log_g(Rn, R, ens['drift']))
        return new, log_ratio, u

    @staticmethod
    def _pick(accept, new, old):
        return {k: jnp.where(accept.reshape((-1,) + (1,) * (new[k].ndim - 1)),
                             new[k], old[k]) for k in ('r', 'log_psi',
                                                      'sign', 'drift',
                                                      'e_loc')}

    def replay_vmc(self, R0, key, steps: int, tau: float):
        """One VMC sub-block from positions R0 with sub-block key ``key``.

        Returns the final ensemble."""
        ens = dict(self.evaluate(R0), r=jnp.asarray(R0, jnp.float32))
        for i in range(steps):
            new, log_ratio, u = self._diffusion(
                ens, jax.random.fold_in(key, i), tau)
            ens = self._pick(jnp.log(u) < log_ratio, new, ens)
        return ens

    @functools.partial(jax.jit, static_argnums=(0, 4, 5))
    def _sweep_block(self, R, M, keys, offset, n_blk, step):
        """Single-electron trials of electrons offset..offset+n_blk-1."""
        s = self.s
        A = self.mo[:n_blk]
        b_ee, b_en, a_en = s.jastrow
        up = jnp.arange(s.n_elec) < s.n_up

        def u_pair(rj, R_w, j):
            d = jnp.sqrt(jnp.sum((rj[None] - R_w) ** 2, -1) + 1e-20)
            a = jnp.where(up == up[j], 0.25, 0.5)
            other = jnp.arange(s.n_elec) != j
            ee = jnp.sum(jnp.where(other, a * d / (1.0 + b_ee * d), 0.0))
            dn = jnp.sqrt(jnp.sum((rj[None] - self.coords) ** 2, -1) + 1e-20)
            an = -self.charges * a_en
            return ee + jnp.sum(an * dn / (1.0 + b_en * dn))

        def move(carry, e):
            R, M = carry
            j = offset + e

            def draw(k):
                ke, ku = jax.random.split(jax.random.fold_in(k, j))
                return (jax.random.normal(ke, (3,), jnp.float32),
                        jax.random.uniform(ku, (), jnp.float32))

            eta, u = jax.vmap(draw)(keys)
            r_old = R[:, j]
            r_new = r_old + step * eta
            phi = mm('oa,wa->wo', A, self._vals(r_new), self.precision)
            ratio = mm('wo,wo->w', M[:, e, :], phi, self.precision)
            dj = jax.vmap(lambda rn, ro, Rw: u_pair(rn, Rw, j)
                          - u_pair(ro, Rw, j))(r_new, r_old, R)
            accept = (jnp.log(jnp.maximum(u, 1e-38))
                      < 2.0 * (jnp.log(jnp.abs(ratio) + 1e-30) + dj))
            # rank-1 update of the inverse: column e of D replaced by phi
            v = mm('weo,wo->we', M, phi, self.precision)
            safe = jnp.where(jnp.abs(ratio) > 1e-20, ratio, 1.0)
            row = M[:, e, :] / safe[:, None]
            Mn = M - v[:, :, None] * row[:, None, :]
            Mn = Mn.at[:, e, :].set(row)
            M = jnp.where(accept[:, None, None], Mn, M)
            R = R.at[:, j].set(jnp.where(accept[:, None], r_new, r_old))
            return (R, M), None

        return jax.lax.scan(move, (R, M), jnp.arange(n_blk))[0]

    def replay_sem(self, R0, key, steps: int, step: float):
        """Sweeps of single-electron moves from positions R0 (fresh
        inverses at the start of every sweep) -> the final ensemble."""
        s = self.s
        R = jnp.asarray(R0, jnp.float32)
        W = R.shape[0]
        ens = self.evaluate(R)
        for i in range(steps):
            kg = jax.random.fold_in(key, i)
            keys = jax.vmap(lambda w: jax.random.fold_in(kg, w))(
                jnp.arange(W))
            R, _ = self._sweep_block(R, ens['minv_up'], keys, 0, s.n_up,
                                     step)
            R, _ = self._sweep_block(R, ens['minv_dn'], keys, s.n_up,
                                     s.n_dn, step)
            ens = self.evaluate(R)
        return dict(ens, r=R)
