/* crc32c (Castagnoli) — hardware crc32 with a slice-by-8 fallback,
 * picked from the CPU's feature bits; native runtime component.
 *
 * Role of reference src/common/crc32c*: ceph_choose_crc32() picks the
 * ISA-L crc32c_intel_fast path on SSE4.2 (and the ARMv8 CRC path on
 * aarch64), else a table.  Here the choice is made once, when the
 * library loads: x86-64 asks __builtin_cpu_supports("sse4.2"), aarch64
 * asks getauxval(AT_HWCAP) & HWCAP_CRC32.  The hardware path runs three
 * interleaved crc32 streams over 3 x 8 KiB blocks, then 3 x 256 B
 * blocks, and merges the lanes with precomputed GF(2) "append N zero
 * bytes" tables (Mark Adler's public-domain crc32c.c scheme); single
 * crc32 instructions take the unaligned head and the tail.  No build
 * flag: the hardware functions carry their own target attribute, so
 * the library runs on any CPU of its architecture.  The Python layer
 * loads it via ctypes (no pybind11 in this image).
 *
 * Polynomial: reflected 0x82F63B78. API: crc32c(seed, buf, len) with the
 * same seed-chaining semantics as ceph_crc32c.  Exports:
 *   ceph_tpu_crc32c        the chosen path
 *   ceph_tpu_crc32c_table  the slice-by-8 table, always
 *   ceph_tpu_crc32c_impl   "sse4.2", "armv8-crc" or "table"
 */

#include <stdint.h>
#include <stddef.h>
#include <string.h>

#define POLY 0x82F63B78u

typedef uint32_t (*crc_fn)(uint32_t, const uint8_t *, size_t);

/* -- slice-by-8 table ---------------------------------------------------- */

static uint32_t T[8][256];

static void init_tables(void) {
    for (int i = 0; i < 256; i++) {
        uint32_t c = (uint32_t)i;
        for (int j = 0; j < 8; j++)
            c = (c & 1) ? (c >> 1) ^ POLY : (c >> 1);
        T[0][i] = c;
    }
    for (int i = 0; i < 256; i++) {
        uint32_t c = T[0][i];
        for (int s = 1; s < 8; s++) {
            c = T[0][c & 0xff] ^ (c >> 8);
            T[s][i] = c;
        }
    }
}

uint32_t ceph_tpu_crc32c_table(uint32_t crc, const uint8_t *buf,
                               size_t len) {
    crc = ~crc;
    while (len && ((uintptr_t)buf & 7)) {
        crc = T[0][(crc ^ *buf++) & 0xff] ^ (crc >> 8);
        len--;
    }
    while (len >= 8) {
        uint64_t w = *(const uint64_t *)buf ^ (uint64_t)crc;
        crc = T[7][w & 0xff] ^ T[6][(w >> 8) & 0xff] ^
              T[5][(w >> 16) & 0xff] ^ T[4][(w >> 24) & 0xff] ^
              T[3][(w >> 32) & 0xff] ^ T[2][(w >> 40) & 0xff] ^
              T[1][(w >> 48) & 0xff] ^ T[0][(w >> 56) & 0xff];
        buf += 8;
        len -= 8;
    }
    while (len--) {
        crc = T[0][(crc ^ *buf++) & 0xff] ^ (crc >> 8);
    }
    return ~crc;
}

/* -- hardware crc32, three streams --------------------------------------- */

#if defined(__x86_64__) && defined(__GNUC__)
#include <nmmintrin.h>
#define HW_NAME "sse4.2"
#define HW_TARGET __attribute__((target("sse4.2")))
HW_TARGET static inline uint64_t hw_u64(uint64_t crc, uint64_t v) {
    return _mm_crc32_u64(crc, v);
}
HW_TARGET static inline uint32_t hw_u8(uint32_t crc, uint8_t v) {
    return _mm_crc32_u8(crc, v);
}
static int hw_supported(void) {
    __builtin_cpu_init();
    return __builtin_cpu_supports("sse4.2");
}
#elif defined(__aarch64__) && defined(__GNUC__)
#include <arm_acle.h>
#include <sys/auxv.h>
#include <asm/hwcap.h>
#define HW_NAME "armv8-crc"
#define HW_TARGET __attribute__((target("+crc")))
HW_TARGET static inline uint64_t hw_u64(uint64_t crc, uint64_t v) {
    return __crc32cd((uint32_t)crc, v);
}
HW_TARGET static inline uint32_t hw_u8(uint32_t crc, uint8_t v) {
    return __crc32cb(crc, v);
}
static int hw_supported(void) {
    return (getauxval(AT_HWCAP) & HWCAP_CRC32) != 0;
}
#endif

#ifdef HW_NAME

/* Block sizes of the three-way split; powers of two.  The crc32
 * instruction has a latency of three cycles and a throughput of one,
 * so three independent streams keep it busy. */
#define LONG 8192
#define SHORT 256

/* zeros[j][b]: the crc register after LONG (SHORT) zero bytes, from a
 * register holding byte b at byte position j */
static uint32_t zeros_long[4][256];
static uint32_t zeros_short[4][256];

static uint32_t gf2_matrix_times(const uint32_t *mat, uint32_t vec) {
    uint32_t sum = 0;
    while (vec) {
        if (vec & 1)
            sum ^= *mat;
        vec >>= 1;
        mat++;
    }
    return sum;
}

static void gf2_matrix_square(uint32_t *square, const uint32_t *mat) {
    for (int n = 0; n < 32; n++)
        square[n] = gf2_matrix_times(mat, mat[n]);
}

/* The operator that appends len zero bytes (len a power of two) to a
 * crc register. */
static void zeros_op(uint32_t *even, size_t len) {
    uint32_t odd[32];
    uint32_t row = 1;
    odd[0] = POLY;                      /* one zero bit */
    for (int n = 1; n < 32; n++) {
        odd[n] = row;
        row <<= 1;
    }
    gf2_matrix_square(even, odd);       /* two zero bits */
    gf2_matrix_square(odd, even);       /* four zero bits */
    /* squaring from here doubles bytes: even holds 1, 4, 16, ... and
     * odd 2, 8, 32, ... zero bytes */
    do {
        gf2_matrix_square(even, odd);
        len >>= 1;
        if (len == 0)
            return;
        gf2_matrix_square(odd, even);
        len >>= 1;
    } while (len);
    for (int n = 0; n < 32; n++)
        even[n] = odd[n];
}

static void zeros_tables(uint32_t zeros[4][256], size_t len) {
    uint32_t op[32];
    zeros_op(op, len);
    for (uint32_t n = 0; n < 256; n++) {
        zeros[0][n] = gf2_matrix_times(op, n);
        zeros[1][n] = gf2_matrix_times(op, n << 8);
        zeros[2][n] = gf2_matrix_times(op, n << 16);
        zeros[3][n] = gf2_matrix_times(op, n << 24);
    }
}

static inline uint32_t shift(uint32_t zeros[4][256], uint32_t crc) {
    return zeros[0][crc & 0xff] ^ zeros[1][(crc >> 8) & 0xff] ^
           zeros[2][(crc >> 16) & 0xff] ^ zeros[3][crc >> 24];
}

static inline uint64_t load64(const uint8_t *p) {
    uint64_t v;
    memcpy(&v, p, 8);                   /* one aligned load */
    return v;
}

HW_TARGET static uint32_t crc32c_hw(uint32_t crc, const uint8_t *buf,
                                    size_t len) {
    uint64_t crc0 = ~crc, crc1, crc2;
    while (len && ((uintptr_t)buf & 7)) {
        crc0 = hw_u8((uint32_t)crc0, *buf++);
        len--;
    }
    while (len >= 3 * LONG) {
        const uint8_t *end = buf + LONG;
        crc1 = crc2 = 0;
        do {
            crc0 = hw_u64(crc0, load64(buf));
            crc1 = hw_u64(crc1, load64(buf + LONG));
            crc2 = hw_u64(crc2, load64(buf + 2 * LONG));
            buf += 8;
        } while (buf < end);
        crc0 = shift(zeros_long, (uint32_t)crc0) ^ (uint32_t)crc1;
        crc0 = shift(zeros_long, (uint32_t)crc0) ^ (uint32_t)crc2;
        buf += 2 * LONG;
        len -= 3 * LONG;
    }
    while (len >= 3 * SHORT) {
        const uint8_t *end = buf + SHORT;
        crc1 = crc2 = 0;
        do {
            crc0 = hw_u64(crc0, load64(buf));
            crc1 = hw_u64(crc1, load64(buf + SHORT));
            crc2 = hw_u64(crc2, load64(buf + 2 * SHORT));
            buf += 8;
        } while (buf < end);
        crc0 = shift(zeros_short, (uint32_t)crc0) ^ (uint32_t)crc1;
        crc0 = shift(zeros_short, (uint32_t)crc0) ^ (uint32_t)crc2;
        buf += 2 * SHORT;
        len -= 3 * SHORT;
    }
    while (len >= 8) {
        crc0 = hw_u64(crc0, load64(buf));
        buf += 8;
        len -= 8;
    }
    while (len--)
        crc0 = hw_u8((uint32_t)crc0, *buf++);
    return ~(uint32_t)crc0;
}

#endif /* HW_NAME */

/* -- choice, once at load ------------------------------------------------ */

static crc_fn chosen = ceph_tpu_crc32c_table;
static const char *chosen_name = "table";

__attribute__((constructor)) static void choose(void) {
    init_tables();
#ifdef HW_NAME
    if (hw_supported()) {
        zeros_tables(zeros_long, LONG);
        zeros_tables(zeros_short, SHORT);
        chosen = crc32c_hw;
        chosen_name = HW_NAME;
    }
#endif
}

uint32_t ceph_tpu_crc32c(uint32_t crc, const uint8_t *buf, size_t len) {
    return chosen(crc, buf, len);
}

const char *ceph_tpu_crc32c_impl(void) {
    return chosen_name;
}
