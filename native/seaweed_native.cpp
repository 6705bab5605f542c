// Host-side native kernels for seaweedfs_tpu.
//
// The reference leans on two Go-assembly SIMD libraries
// (klauspost/crc32, klauspost/reedsolomon — SURVEY.md §2.2 ⚡ rows).
// This file provides the equivalent native host paths for our build:
//
//   sw_crc32c    — CRC32-C: SSE4.2 hardware instruction when available,
//                  slice-by-8 tables otherwise.
//   sw_gf_mul_add/sw_gf_mix — GF(2^8) region multiply-accumulate with the
//                  AVX2 PSHUFB split-nibble technique (the same scheme
//                  klauspost/ISA-L use), scalar table fallback.
//
// The TPU Pallas kernel is the hot path for bulk EC; these serve the host
// daemon (checksums on ingest) and the CPU-baseline benchmark.
//
// Build: make -C native   ->  libseaweed_native.so

#include <cstddef>
#include <cstdint>
#include <cstring>

#include <cerrno>
#include <unistd.h>

#if defined(__x86_64__) || defined(_M_X64)
#include <immintrin.h>
#define SW_X86 1
#endif

extern "C" {

// ---------------------------------------------------------------------------
// CRC32-C
// ---------------------------------------------------------------------------

static uint32_t crc_tables[8][256];
static bool crc_tables_ready = false;

static void init_crc_tables() {
    if (crc_tables_ready) return;
    const uint32_t poly = 0x82F63B78u;  // reversed Castagnoli
    for (int i = 0; i < 256; i++) {
        uint32_t crc = (uint32_t)i;
        for (int k = 0; k < 8; k++)
            crc = (crc >> 1) ^ ((crc & 1) ? poly : 0);
        crc_tables[0][i] = crc;
    }
    for (int t = 1; t < 8; t++)
        for (int i = 0; i < 256; i++)
            crc_tables[t][i] = (crc_tables[t - 1][i] >> 8) ^
                               crc_tables[0][crc_tables[t - 1][i] & 0xFF];
    crc_tables_ready = true;
}

static uint32_t crc32c_sw(uint32_t crc, const uint8_t* buf, size_t len) {
    init_crc_tables();
    crc = ~crc;
    while (len >= 8) {
        uint64_t word;
        memcpy(&word, buf, 8);
        crc ^= (uint32_t)word;
        uint32_t hi = (uint32_t)(word >> 32);
        crc = crc_tables[7][crc & 0xFF] ^ crc_tables[6][(crc >> 8) & 0xFF] ^
              crc_tables[5][(crc >> 16) & 0xFF] ^ crc_tables[4][crc >> 24] ^
              crc_tables[3][hi & 0xFF] ^ crc_tables[2][(hi >> 8) & 0xFF] ^
              crc_tables[1][(hi >> 16) & 0xFF] ^ crc_tables[0][hi >> 24];
        buf += 8;
        len -= 8;
    }
    while (len--) crc = (crc >> 8) ^ crc_tables[0][(crc ^ *buf++) & 0xFF];
    return ~crc;
}

#ifdef SW_X86
__attribute__((target("sse4.2")))
static uint32_t crc32c_hw(uint32_t crc, const uint8_t* buf, size_t len) {
    uint64_t c = ~crc;
    while (len >= 8) {
        uint64_t word;
        memcpy(&word, buf, 8);
        c = _mm_crc32_u64(c, word);
        buf += 8;
        len -= 8;
    }
    uint32_t c32 = (uint32_t)c;
    while (len--) c32 = _mm_crc32_u8(c32, *buf++);
    return ~c32;
}
#endif

uint32_t sw_crc32c(uint32_t crc, const uint8_t* buf, size_t len) {
#ifdef SW_X86
    if (__builtin_cpu_supports("sse4.2")) return crc32c_hw(crc, buf, len);
#endif
    return crc32c_sw(crc, buf, len);
}

// ---------------------------------------------------------------------------
// GF(2^8) region ops (poly 0x11D)
// ---------------------------------------------------------------------------

static uint8_t gf_mul_table[256][256];
static uint8_t gf_nib_lo[256][16];  // c * low-nibble values
static uint8_t gf_nib_hi[256][16];  // c * (high-nibble << 4) values
static bool gf_ready = false;

static uint8_t gf_mul_slow(uint8_t a, uint8_t b) {
    uint16_t r = 0, aa = a;
    while (b) {
        if (b & 1) r ^= aa;
        aa <<= 1;
        if (aa & 0x100) aa ^= 0x11D;
        b >>= 1;
    }
    return (uint8_t)r;
}

static void init_gf_tables() {
    if (gf_ready) return;
    for (int a = 0; a < 256; a++)
        for (int b = 0; b < 256; b++)
            gf_mul_table[a][b] = gf_mul_slow((uint8_t)a, (uint8_t)b);
    for (int c = 0; c < 256; c++) {
        for (int n = 0; n < 16; n++) {
            gf_nib_lo[c][n] = gf_mul_table[c][n];
            gf_nib_hi[c][n] = gf_mul_table[c][n << 4];
        }
    }
    gf_ready = true;
}

static void gf_mul_add_scalar(uint8_t c, const uint8_t* src, uint8_t* dst,
                              size_t n) {
    const uint8_t* row = gf_mul_table[c];
    for (size_t i = 0; i < n; i++) dst[i] ^= row[src[i]];
}

#ifdef SW_X86
__attribute__((target("avx2")))
static void gf_mul_add_avx2(uint8_t c, const uint8_t* src, uint8_t* dst,
                            size_t n) {
    __m256i lo_tbl = _mm256_broadcastsi128_si256(
        _mm_loadu_si128((const __m128i*)gf_nib_lo[c]));
    __m256i hi_tbl = _mm256_broadcastsi128_si256(
        _mm_loadu_si128((const __m128i*)gf_nib_hi[c]));
    __m256i mask = _mm256_set1_epi8(0x0F);
    size_t i = 0;
    for (; i + 32 <= n; i += 32) {
        __m256i v = _mm256_loadu_si256((const __m256i*)(src + i));
        __m256i lo = _mm256_and_si256(v, mask);
        __m256i hi = _mm256_and_si256(_mm256_srli_epi16(v, 4), mask);
        __m256i prod = _mm256_xor_si256(_mm256_shuffle_epi8(lo_tbl, lo),
                                        _mm256_shuffle_epi8(hi_tbl, hi));
        __m256i d = _mm256_loadu_si256((const __m256i*)(dst + i));
        _mm256_storeu_si256((__m256i*)(dst + i),
                            _mm256_xor_si256(d, prod));
    }
    if (i < n) gf_mul_add_scalar(c, src + i, dst + i, n - i);
}
#endif

// dst ^= c * src over GF(2^8)
void sw_gf_mul_add(uint8_t c, const uint8_t* src, uint8_t* dst, size_t n) {
    init_gf_tables();
    if (c == 0) return;
#ifdef SW_X86
    if (__builtin_cpu_supports("avx2")) {
        gf_mul_add_avx2(c, src, dst, n);
        return;
    }
#endif
    gf_mul_add_scalar(c, src, dst, n);
}

// outs[r] = XOR_c mat[r*cols + c] * ins[c], each region n bytes.
void sw_gf_mix(const uint8_t* mat, int rows, int cols,
               const uint8_t* const* ins, uint8_t* const* outs, size_t n) {
    init_gf_tables();
    for (int r = 0; r < rows; r++) {
        memset(outs[r], 0, n);
        for (int c = 0; c < cols; c++) {
            uint8_t coef = mat[r * cols + c];
            if (coef) sw_gf_mul_add(coef, ins[c], outs[r], n);
        }
    }
}

// dsts[i] gets lens[i] bytes of file fds[i] from offs[i], for n reads in
// one call: an EC needle's shard intervals, and the ten survivors of a
// degraded read's gather (ec/degraded.py), behind ONE release of the
// interpreter's lock.  got[i] is what read i got (short at a file's
// end, -1 on an error).
void sw_pread_rows(int n, const int* fds, const int64_t* offs,
                   uint8_t* const* dsts, const int64_t* lens,
                   int64_t* got) {
    for (int i = 0; i < n; i++) {
        size_t done = 0, len = (size_t)lens[i];
        while (done < len) {
            ssize_t r = pread(fds[i], dsts[i] + done, len - done,
                              (off_t)(offs[i] + (int64_t)done));
            if (r < 0 && errno == EINTR) continue;
            if (r <= 0) {
                if (r < 0 && done == 0) done = (size_t)-1;
                break;
            }
            done += (size_t)r;
        }
        got[i] = (int64_t)done;
    }
}

}  // extern "C"
