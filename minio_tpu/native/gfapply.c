/* GF(2^8) matrix application over shard rows — the native host engine
 * behind minio_tpu.ops.gf_native (counterpart of the reference's
 * klauspost/reedsolomon AVX2 galois loops, used at
 * /root/reference/cmd/erasure-coding.go:62,76-108).
 *
 * Algorithm: split-nibble lookup ("Screaming Fast Galois Field
 * Arithmetic", Plank et al.) — for each coding coefficient c two 16-entry
 * tables T_lo[n]=c*n and T_hi[n]=c*(n<<4) turn a GF multiply into two
 * byte shuffles and an XOR. The tables arrive precomputed from Python
 * (ops/gf.py owns the field math; poly 0x11D), so this file is pure data
 * movement. With SSSE3+ the shuffles compile to pshufb via GCC vector
 * extensions; a scalar fallback covers other ISAs.
 *
 * Layout: tables[r][k][2][16] (lo, hi per coefficient), in[k][s] and
 * out[r][s] row-major contiguous.
 */

#include <stddef.h>
#include <stdint.h>
#include <string.h>

#if defined(_OPENMP)
#include <omp.h>
#endif

#if defined(__GFNI__) && defined(__AVX512BW__) && defined(__AVX512F__)
#define GF_HAVE_GFNI512 1
#include <immintrin.h>
#elif defined(__SSSE3__) || defined(__AVX2__)
#define GF_HAVE_SHUFFLE 1
#include <tmmintrin.h>
#endif

/* Engine actually compiled in: 2 = GFNI/AVX-512 affine, 1 = SSSE3
 * nibble-shuffle, 0 = scalar nibble tables. Python picks the matching
 * precomputed operand (affine qwords vs nibble tables). */
int gf_engine_kind(void) {
#if defined(GF_HAVE_GFNI512)
    return 2;
#elif defined(GF_HAVE_SHUFFLE)
    return 1;
#else
    return 0;
#endif
}

/* Team size for a batch of nblocks blocks of block_bytes input each:
 * never more threads than blocks (the loop is parallel across blocks,
 * and an idle team member still has to be woken and joined), and one
 * thread per 256 KiB of input — the floor gf_apply_affine uses — since
 * below that waking a thread costs more than the work it takes over. */
static int gf_batch_team(int nthreads, size_t nblocks, size_t block_bytes) {
    size_t team = nblocks * block_bytes / (size_t)(256 << 10);
    if (team > nblocks)
        team = nblocks;
    if (nthreads < 1 || team < 1)
        return 1;
    return team < (size_t)nthreads ? (int)team : nthreads;
}

#ifdef GF_HAVE_GFNI512
/* GFNI path: each coding coefficient c is an 8x8 GF(2) bit matrix (the
 * same expansion ops/gf.py bit_matrix feeds the MXU); vgf2p8affineqb
 * applies it to 64 data bytes per instruction. qwords[r][k] holds the
 * matrices in the instruction's byte order (built host-side in
 * ops/gf_native.py, validated bit-exact in tests). */
static void gf_affine_cols(const uint64_t *qwords, int r, int k,
                           const uint8_t *in, uint8_t *out, size_t s,
                           size_t c0, size_t c1) {
    __attribute__((aligned(64))) uint8_t accbuf[64];
    size_t c = c0;
    for (; c + 64 <= c1; c += 64) {
        for (int rr = 0; rr < r; rr++) {
            __m512i acc = _mm512_setzero_si512();
            const uint64_t *qrow = qwords + (size_t)rr * k;
            for (int j = 0; j < k; j++) {
                __m512i x = _mm512_loadu_si512(
                    (const void *)(in + (size_t)j * s + c));
                __m512i a = _mm512_set1_epi64((long long)qrow[j]);
                acc = _mm512_xor_si512(
                    acc, _mm512_gf2p8affine_epi64_epi8(x, a, 0));
            }
            _mm512_storeu_si512((void *)(out + (size_t)rr * s + c), acc);
        }
    }
    if (c < c1) {
        /* Tail: stage the ragged columns through a 64-byte buffer. */
        size_t tail = c1 - c;
        __attribute__((aligned(64))) uint8_t xin[64];
        for (int rr = 0; rr < r; rr++) {
            __m512i acc = _mm512_setzero_si512();
            const uint64_t *qrow = qwords + (size_t)rr * k;
            for (int j = 0; j < k; j++) {
                memset(xin, 0, 64);
                memcpy(xin, in + (size_t)j * s + c, tail);
                __m512i x = _mm512_load_si512((const void *)xin);
                __m512i a = _mm512_set1_epi64((long long)qrow[j]);
                acc = _mm512_xor_si512(
                    acc, _mm512_gf2p8affine_epi64_epi8(x, a, 0));
            }
            _mm512_store_si512((void *)accbuf, acc);
            memcpy(out + (size_t)rr * s + c, accbuf, tail);
        }
    }
}

void gf_apply_affine(const uint64_t *qwords, int r, int k, const uint8_t *in,
                     uint8_t *out, size_t s, int nthreads) {
    if (nthreads < 1)
        nthreads = 1;
    if ((size_t)k * s < (size_t)(256 << 10))
        nthreads = 1;
#if defined(_OPENMP)
#pragma omp parallel for num_threads(nthreads) schedule(static)
#endif
    for (int t = 0; t < nthreads; t++) {
        size_t chunk = (s + (size_t)nthreads - 1) / (size_t)nthreads;
        chunk = (chunk + 63) & ~(size_t)63;
        size_t c0 = (size_t)t * chunk;
        size_t c1 = c0 + chunk;
        if (c0 > s)
            c0 = s;
        if (c1 > s)
            c1 = s;
        if (c0 < c1)
            gf_affine_cols(qwords, r, k, in, out, s, c0, c1);
    }
}

void gf_apply_affine_batch(const uint64_t *qwords, int r, int k,
                           const uint8_t *in, uint8_t *out, size_t nblocks,
                           size_t s, int nthreads) {
    nthreads = gf_batch_team(nthreads, nblocks, (size_t)k * s);
#if defined(_OPENMP)
#pragma omp parallel for num_threads(nthreads) schedule(dynamic, 1)
#endif
    for (size_t b = 0; b < nblocks; b++) {
        gf_affine_cols(qwords, r, k, in + b * (size_t)k * s,
                       out + b * (size_t)r * s, s, 0, s);
    }
}
#else
/* Keep the symbols resolvable; Python checks gf_engine_kind() first. */
void gf_apply_affine(const uint64_t *qwords, int r, int k, const uint8_t *in,
                     uint8_t *out, size_t s, int nthreads) {
    (void)qwords; (void)r; (void)k; (void)in; (void)out; (void)s;
    (void)nthreads;
}
void gf_apply_affine_batch(const uint64_t *qwords, int r, int k,
                           const uint8_t *in, uint8_t *out, size_t nblocks,
                           size_t s, int nthreads) {
    (void)qwords; (void)r; (void)k; (void)in; (void)out; (void)nblocks;
    (void)s; (void)nthreads;
}
#endif

static void gf_apply_cols(const uint8_t *tables, int r, int k,
                          const uint8_t *in, uint8_t *out, size_t s,
                          size_t c0, size_t c1) {
    for (int rr = 0; rr < r; rr++) {
        uint8_t *dst = out + (size_t)rr * s;
        size_t c = c0;
#ifdef GF_HAVE_SHUFFLE
        const __m128i mask = _mm_set1_epi8(0x0f);
        for (; c + 16 <= c1; c += 16) {
            __m128i acc = _mm_setzero_si128();
            for (int j = 0; j < k; j++) {
                const uint8_t *t = tables + (((size_t)rr * k + j) * 2) * 16;
                __m128i tlo = _mm_loadu_si128((const __m128i *)t);
                __m128i thi = _mm_loadu_si128((const __m128i *)(t + 16));
                __m128i x = _mm_loadu_si128(
                    (const __m128i *)(in + (size_t)j * s + c));
                __m128i lo = _mm_and_si128(x, mask);
                __m128i hi = _mm_and_si128(_mm_srli_epi64(x, 4), mask);
                acc = _mm_xor_si128(acc, _mm_shuffle_epi8(tlo, lo));
                acc = _mm_xor_si128(acc, _mm_shuffle_epi8(thi, hi));
            }
            _mm_storeu_si128((__m128i *)(dst + c), acc);
        }
#endif
        for (; c < c1; c++) {
            uint8_t acc = 0;
            for (int j = 0; j < k; j++) {
                const uint8_t *t = tables + (((size_t)rr * k + j) * 2) * 16;
                uint8_t x = in[(size_t)j * s + c];
                acc ^= t[x & 15] ^ t[16 + (x >> 4)];
            }
            dst[c] = acc;
        }
    }
}

void gf_apply(const uint8_t *tables, int r, int k, const uint8_t *in,
              uint8_t *out, size_t s, int nthreads) {
    if (nthreads < 1)
        nthreads = 1;
    /* Below ~64 KiB of work the fork/join overhead beats the speedup. */
    if ((size_t)k * s < (size_t)(64 << 10))
        nthreads = 1;
#if defined(_OPENMP)
#pragma omp parallel for num_threads(nthreads) schedule(static)
#endif
    for (int t = 0; t < nthreads; t++) {
        size_t chunk = (s + (size_t)nthreads - 1) / (size_t)nthreads;
        /* Keep vector alignment friendly: round chunks to 16. */
        chunk = (chunk + 15) & ~(size_t)15;
        size_t c0 = (size_t)t * chunk;
        size_t c1 = c0 + chunk;
        if (c0 > s)
            c0 = s;
        if (c1 > s)
            c1 = s;
        if (c0 < c1)
            gf_apply_cols(tables, r, k, in, out, s, c0, c1);
    }
}

/* Batched variant: in[b][k][s], out[b][r][s]; parallel across blocks. */
void gf_apply_batch(const uint8_t *tables, int r, int k, const uint8_t *in,
                    uint8_t *out, size_t nblocks, size_t s, int nthreads) {
    nthreads = gf_batch_team(nthreads, nblocks, (size_t)k * s);
#if defined(_OPENMP)
#pragma omp parallel for num_threads(nthreads) schedule(dynamic, 1)
#endif
    for (size_t b = 0; b < nblocks; b++) {
        gf_apply_cols(tables, r, k, in + b * (size_t)k * s,
                      out + b * (size_t)r * s, s, 0, s);
    }
}
