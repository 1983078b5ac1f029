"""Plain reference of ML-KEM-768 encapsulation, transcribed from FIPS 203.

Pure Python with per-coefficient loops: the FIPS-order in-place NTT,
SampleNTT by rejection from SHAKE128, CBD sampling, ByteEncode /
ByteDecode, Compress / Decompress, and ML-KEM.Encaps_internal(ek, m).
It shares no code with the system under test.

``rounding=False`` gives the control: Compress floors where FIPS 203
rounds, which breaks the standard's ciphertext format.
"""
from __future__ import annotations

import hashlib

Q = 3329
N = 256
K = 3
ETA1 = 2
ETA2 = 2
DU = 10
DV = 4


def _bitrev7(x: int) -> int:
    r = 0
    for _ in range(7):
        r = (r << 1) | (x & 1)
        x >>= 1
    return r


ZETAS = [pow(17, _bitrev7(i), Q) for i in range(128)]
GAMMAS = [pow(17, 2 * _bitrev7(i) + 1, Q) for i in range(128)]


def ntt(f: list[int]) -> list[int]:
    f = list(f)
    k = 1
    ln = 128
    while ln >= 2:
        for start in range(0, N, 2 * ln):
            z = ZETAS[k]
            k += 1
            for j in range(start, start + ln):
                t = z * f[j + ln] % Q
                f[j + ln] = (f[j] - t) % Q
                f[j] = (f[j] + t) % Q
        ln //= 2
    return f


def intt(f: list[int]) -> list[int]:
    f = list(f)
    k = 127
    ln = 2
    while ln <= 128:
        for start in range(0, N, 2 * ln):
            z = ZETAS[k]
            k -= 1
            for j in range(start, start + ln):
                t = f[j]
                f[j] = (t + f[j + ln]) % Q
                f[j + ln] = z * (f[j + ln] - t) % Q
        ln *= 2
    return [x * 3303 % Q for x in f]    # 3303 = 128^-1 mod q


def basemul(f: list[int], g: list[int]) -> list[int]:
    h = [0] * N
    for i in range(128):
        a0, a1 = f[2 * i], f[2 * i + 1]
        b0, b1 = g[2 * i], g[2 * i + 1]
        h[2 * i] = (a0 * b0 + a1 * b1 % Q * GAMMAS[i]) % Q
        h[2 * i + 1] = (a0 * b1 + a1 * b0) % Q
    return h


def sample_ntt(seed: bytes) -> list[int]:
    xof = hashlib.shake_128(seed)
    need = 3 * 168
    while True:
        buf = xof.digest(need)
        out = []
        for o in range(0, len(buf) - 2, 3):
            d1 = buf[o] + 256 * (buf[o + 1] % 16)
            d2 = (buf[o + 1] // 16) + 16 * buf[o + 2]
            for d in (d1, d2):
                if d < Q and len(out) < N:
                    out.append(d)
            if len(out) == N:
                return out
        need *= 2


def sample_cbd(eta: int, buf: bytes) -> list[int]:
    bits = []
    for byte in buf:
        for l in range(8):
            bits.append((byte >> l) & 1)
    f = []
    for i in range(N):
        x = sum(bits[2 * i * eta + j] for j in range(eta))
        y = sum(bits[2 * i * eta + eta + j] for j in range(eta))
        f.append((x - y) % Q)
    return f


def byte_encode(d: int, f: list[int]) -> bytes:
    bits = []
    for a in f:
        for j in range(d):
            bits.append((a >> j) & 1)
    out = bytearray(32 * d)
    for i, bit in enumerate(bits):
        out[i // 8] |= bit << (i % 8)
    return bytes(out)


def byte_decode(d: int, buf: bytes) -> list[int]:
    bits = []
    for byte in buf:
        for l in range(8):
            bits.append((byte >> l) & 1)
    return [sum(bits[i * d + j] << j for j in range(d)) for i in range(N)]


def compress(d: int, x: int, rounding: bool = True) -> int:
    if rounding:
        return ((x * (1 << (d + 1)) + Q) // (2 * Q)) % (1 << d)
    return (x * (1 << d) // Q) % (1 << d)


def decompress(d: int, y: int) -> int:
    return (Q * y + (1 << (d - 1))) >> d


def _g(data: bytes):
    dig = hashlib.sha3_512(data).digest()
    return dig[:32], dig[32:]


def _h(data: bytes) -> bytes:
    return hashlib.sha3_256(data).digest()


def _prf(eta: int, s: bytes, b: int) -> bytes:
    return hashlib.shake_256(s + bytes([b])).digest(64 * eta)


def _expand_a(rho: bytes):
    return [[sample_ntt(rho + bytes([j, i])) for j in range(K)]
            for i in range(K)]


def k_pke_encrypt(ek: bytes, m: bytes, r: bytes,
                  rounding: bool = True) -> bytes:
    t_hat = [byte_decode(12, ek[384 * i:384 * (i + 1)]) for i in range(K)]
    rho = ek[384 * K:]
    a = _expand_a(rho)
    y = [sample_cbd(ETA1, _prf(ETA1, r, i)) for i in range(K)]
    e1 = [sample_cbd(ETA2, _prf(ETA2, r, K + i)) for i in range(K)]
    e2 = sample_cbd(ETA2, _prf(ETA2, r, 2 * K))
    y_hat = [ntt(v) for v in y]
    u = []
    for i in range(K):
        acc = [0] * N
        for j in range(K):
            p = basemul(a[j][i], y_hat[j])      # A transposed
            acc = [(x + v) % Q for x, v in zip(acc, p)]
        u.append([(x + v) % Q for x, v in zip(intt(acc), e1[i])])
    mu = [decompress(1, b) for b in byte_decode(1, m)]
    acc = [0] * N
    for j in range(K):
        p = basemul(t_hat[j], y_hat[j])
        acc = [(x + v) % Q for x, v in zip(acc, p)]
    v = [(x + a2 + b2) % Q for x, a2, b2 in zip(intt(acc), e2, mu)]
    c1 = b"".join(byte_encode(DU, [compress(DU, x, rounding) for x in ui])
                  for ui in u)
    c2 = byte_encode(DV, [compress(DV, x, rounding) for x in v])
    return c1 + c2


def encaps(ek: bytes, m: bytes, rounding: bool = True):
    """ML-KEM.Encaps_internal: (shared key K, ciphertext c)."""
    key, r = _g(m + _h(ek))
    return key, k_pke_encrypt(ek, m, r, rounding)
