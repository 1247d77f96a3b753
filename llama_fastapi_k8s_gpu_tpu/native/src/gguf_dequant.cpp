// Native GGUF dequantization: the in-tree C++ analogue of the reference's
// native engine dependency (llama-cpp-python==0.2.77 C/CUDA kernels, reference
// docker/Dockerfile.base:30-32).  The TPU framework keeps the *compute* path
// in JAX/XLA/Pallas; this library accelerates the host-side load path — the
// multi-GB GGUF -> float32 conversion that happens once at model load —
// with multithreaded scalar kernels that g++ auto-vectorizes.
//
// Contract: bit-exact with the numpy reference codecs in gguf/quants.py
// (enforced by tests/test_native.py).  All arithmetic is float32 with the
// same operation order as the numpy expressions.
//
// C ABI (ctypes-friendly):
//   int lfkt_dequant(int ggml_type, const uint8_t* src, int64_t n_elements,
//                    float* dst, int n_threads);
//     returns 0 on success, -1 for unsupported type, -2 for bad args.
//   int lfkt_supported(int ggml_type);  // 1 if the type is handled

#include <algorithm>
#include <cstdint>
#include <cstring>
#include <thread>
#include <vector>

namespace {

// ---- ggml type codes (gguf/constants.py GGMLType) --------------------------
enum GgmlType : int {
  T_F32 = 0,
  T_F16 = 1,
  T_Q4_0 = 2,
  T_Q8_0 = 8,
  T_Q4_K = 12,
  T_Q5_K = 13,
  T_Q6_K = 14,
  T_BF16 = 30,
};

constexpr int QK_K = 256;

// ---- IEEE f16 -> f32 (exact, matches numpy's astype) -----------------------
float f16_to_f32_slow(uint16_t h) {
  uint32_t sign = static_cast<uint32_t>(h & 0x8000u) << 16;
  uint32_t exp = (h >> 10) & 0x1Fu;
  uint32_t man = h & 0x3FFu;
  uint32_t bits;
  if (exp == 0) {
    if (man == 0) {
      bits = sign;  // +-0
    } else {        // subnormal: renormalize
      int e = -1;
      uint32_t m = man;
      do {
        e++;
        m <<= 1;
      } while (!(m & 0x400u));
      m &= 0x3FFu;
      bits = sign | (static_cast<uint32_t>(127 - 15 - e) << 23) | (m << 13);
    }
  } else if (exp == 31) {
    bits = sign | 0x7F800000u | (man << 13);  // inf / nan
  } else {
    bits = sign | ((exp - 15 + 127) << 23) | (man << 13);
  }
  float f;
  std::memcpy(&f, &bits, 4);
  return f;
}

// one 256 KiB table beats per-element bit twiddling on the load path
struct F16Table {
  float v[65536];
  F16Table() {
    for (uint32_t i = 0; i < 65536; i++) v[i] = f16_to_f32_slow(static_cast<uint16_t>(i));
  }
};
const F16Table kF16;

inline float f16(const uint8_t* p) {
  uint16_t h;
  std::memcpy(&h, p, 2);
  return kF16.v[h];
}

// ---- per-block kernels (layouts: gguf/quants.py:15-24) ---------------------

// Q8_0  block=32: f16 d | 32 x i8
void deq_q8_0(const uint8_t* b, float* y) {
  const float d = f16(b);
  const int8_t* q = reinterpret_cast<const int8_t*>(b + 2);
  for (int i = 0; i < 32; i++) y[i] = d * static_cast<float>(q[i]);
}

// Q4_0  block=32: f16 d | 16 B nibbles; elements 0..15 = lo, 16..31 = hi
void deq_q4_0(const uint8_t* b, float* y) {
  const float d = f16(b);
  const uint8_t* qs = b + 2;
  for (int i = 0; i < 16; i++) {
    y[i] = d * (static_cast<float>(qs[i] & 0x0F) - 8.0f);
    y[i + 16] = d * (static_cast<float>(qs[i] >> 4) - 8.0f);
  }
}

// shared K-quant 6-bit scale/min unpack (gguf/quants.py unpack_scale_min_k4)
inline void scale_min_k4(const uint8_t* s, uint8_t* sc, uint8_t* mn) {
  for (int j = 0; j < 4; j++) {
    sc[j] = s[j] & 63;
    mn[j] = s[j + 4] & 63;
  }
  for (int j = 4; j < 8; j++) {
    sc[j] = static_cast<uint8_t>((s[j + 4] & 0x0F) | ((s[j - 4] >> 6) << 4));
    mn[j] = static_cast<uint8_t>((s[j + 4] >> 4) | ((s[j] >> 6) << 4));
  }
}

// Q4_K  block=256 (144 B): f16 d | f16 dmin | 12 B scales | 128 B nibbles
// sub-block 2g from low nibble of qs[32g..32g+32), 2g+1 from high nibble
void deq_q4_k(const uint8_t* b, float* y) {
  const float d = f16(b);
  const float dmin = f16(b + 2);
  uint8_t sc[8], mn[8];
  scale_min_k4(b + 4, sc, mn);
  const uint8_t* qs = b + 16;
  for (int g = 0; g < 4; g++) {
    const float s_lo = d * static_cast<float>(sc[2 * g]);
    const float m_lo = dmin * static_cast<float>(mn[2 * g]);
    const float s_hi = d * static_cast<float>(sc[2 * g + 1]);
    const float m_hi = dmin * static_cast<float>(mn[2 * g + 1]);
    const uint8_t* q = qs + 32 * g;
    float* lo = y + 64 * g;
    float* hi = lo + 32;
    for (int i = 0; i < 32; i++) {
      lo[i] = s_lo * static_cast<float>(q[i] & 0x0F) - m_lo;
      hi[i] = s_hi * static_cast<float>(q[i] >> 4) - m_hi;
    }
  }
}

// Q5_K  block=256 (176 B): f16 d | f16 dmin | 12 B scales | 32 B qh | 128 B qs
// sub-block j: low/high nibble as Q4_K, plus 16 * ((qh >> j) & 1)
void deq_q5_k(const uint8_t* b, float* y) {
  const float d = f16(b);
  const float dmin = f16(b + 2);
  uint8_t sc[8], mn[8];
  scale_min_k4(b + 4, sc, mn);
  const uint8_t* qh = b + 16;
  const uint8_t* qs = b + 48;
  for (int g = 0; g < 4; g++) {
    const int j_lo = 2 * g, j_hi = 2 * g + 1;
    const float s_lo = d * static_cast<float>(sc[j_lo]);
    const float m_lo = dmin * static_cast<float>(mn[j_lo]);
    const float s_hi = d * static_cast<float>(sc[j_hi]);
    const float m_hi = dmin * static_cast<float>(mn[j_hi]);
    const uint8_t* q = qs + 32 * g;
    float* lo = y + 64 * g;
    float* hi = lo + 32;
    for (int i = 0; i < 32; i++) {
      const int h_lo = (qh[i] >> j_lo) & 1;
      const int h_hi = (qh[i] >> j_hi) & 1;
      lo[i] = s_lo * static_cast<float>((q[i] & 0x0F) + 16 * h_lo) - m_lo;
      hi[i] = s_hi * static_cast<float>((q[i] >> 4) + 16 * h_hi) - m_hi;
    }
  }
}

// Q6_K  block=256 (210 B): 128 B ql | 64 B qh | 16 x i8 scales | f16 d
// two 128-element halves; within a half, element l (0..127):
//   low  = (l < 64 ? ql[l] & 0xF : ql[l-64] >> 4)
//   high = (qh[l % 32] >> (2 * (l / 32))) & 3
//   q    = (low | high << 4) - 32, sub-block scale sc[l / 16]
void deq_q6_k(const uint8_t* b, float* y) {
  const int8_t* scales = reinterpret_cast<const int8_t*>(b + 192);
  const float d = f16(b + 208);
  for (int half = 0; half < 2; half++) {
    const uint8_t* ql = b + 64 * half;
    const uint8_t* qh = b + 128 + 32 * half;
    float* yo = y + 128 * half;
    for (int l = 0; l < 128; l++) {
      const int low = (l < 64) ? (ql[l] & 0x0F) : (ql[l - 64] >> 4);
      const int high = (qh[l & 31] >> (2 * (l >> 5))) & 3;
      const int q = (low | (high << 4)) - 32;
      const float dsc =
          d * static_cast<float>(scales[8 * half + (l >> 4)]);
      yo[l] = dsc * static_cast<float>(q);
    }
  }
}

// ---- format table ----------------------------------------------------------
struct Fmt {
  int type;
  int64_t block_elems;
  int64_t block_bytes;
  void (*fn)(const uint8_t*, float*);
};

const Fmt kFmts[] = {
    {T_Q8_0, 32, 34, deq_q8_0},
    {T_Q4_0, 32, 18, deq_q4_0},
    {T_Q4_K, QK_K, 144, deq_q4_k},
    {T_Q5_K, QK_K, 176, deq_q5_k},
    {T_Q6_K, QK_K, 210, deq_q6_k},
};

const Fmt* find_fmt(int type) {
  for (const Fmt& f : kFmts)
    if (f.type == type) return &f;
  return nullptr;
}

// ---- float formats (threaded memcpy/convert) -------------------------------
void conv_range_f32(const uint8_t* src, float* dst, int64_t lo, int64_t hi) {
  std::memcpy(dst + lo, src + 4 * lo, 4 * static_cast<size_t>(hi - lo));
}

void conv_range_f16(const uint8_t* src, float* dst, int64_t lo, int64_t hi) {
  for (int64_t i = lo; i < hi; i++) dst[i] = f16(src + 2 * i);
}

void conv_range_bf16(const uint8_t* src, float* dst, int64_t lo, int64_t hi) {
  for (int64_t i = lo; i < hi; i++) {
    uint16_t h;
    std::memcpy(&h, src + 2 * i, 2);
    uint32_t bits = static_cast<uint32_t>(h) << 16;
    std::memcpy(dst + i, &bits, 4);
  }
}

template <typename F>
void run_threads(int64_t n_units, int n_threads, F&& body) {
  if (n_threads <= 1 || n_units < 2 * n_threads) {
    body(0, n_units);
    return;
  }
  std::vector<std::thread> ts;
  ts.reserve(static_cast<size_t>(n_threads));
  const int64_t per = (n_units + n_threads - 1) / n_threads;
  for (int t = 0; t < n_threads; t++) {
    const int64_t lo = t * per;
    const int64_t hi = std::min<int64_t>(lo + per, n_units);
    if (lo >= hi) break;
    ts.emplace_back([&body, lo, hi] { body(lo, hi); });
  }
  for (auto& t : ts) t.join();
}

// ---- fused-kernel layout packers (ops/pallas/qmatmul.py prep_q4k,
// ---- ops/pallas/q6matmul.py prep_q6k) --------------------------------------
//
// The Pallas serving path keeps K-quant weights packed in HBM; the host-side
// packers reorder raw GGUF block bytes into the kernels' tile-local
// element-major layout.  The numpy reference implementations are a chain of
// full-tensor reshape/transpose passes — single-threaded and allocation
// heavy, measured as the dominant cost of an 8B cold start.  These kernels
// produce bit-identical planes (qs/q4/q2 int8 exact; sm/sm6 bf16 via
// round-to-nearest-even, matching XLA's f32->bf16 cast) in one pass per row,
// threaded over rows.

inline uint16_t bf16_rne(float f) {
  uint32_t b;
  std::memcpy(&b, &f, 4);
  if ((b & 0x7FFFFFFFu) > 0x7F800000u)  // NaN -> XLA's quiet NaN, sign kept
    return static_cast<uint16_t>(((b >> 16) & 0x8000u) | 0x7FC0u);
  b += 0x7FFFu + ((b >> 16) & 1u);
  return static_cast<uint16_t>(b >> 16);
}

constexpr int64_t TKQ = 2048;  // K elements per kernel tile (= 8 super-blocks)

// A K that ends in a TAIL tile (ops/pallas/qmatmul.py tail_of: 512 or 1024
// columns after the whole 2048 tiles) stores that tile in planes of its own,
// the same layout at its own period: `nsb` super-blocks a tile (8 whole, 2 or
// 4 a tail), columns element-major over the tile's own sub-blocks, its scales
// tiled up to the plane's lanes.

// One Q4_K tile of `nsb` super-blocks at `blk0` -> qt (nsb*128 bytes: byte
// e*S + s, S = nsb*8, packs sub-block s's elements e (lo) and e+16 (hi) as
// (hi-8)*16 + lo) + smt (128 bf16: the S scales tiled to 64 lanes, then the
// S mins).
void prep_q4k_tile(const uint8_t* blk0, int nsb, int8_t* qt, uint16_t* smt) {
  const int S = nsb * 8;
  for (int sb = 0; sb < nsb; sb++) {
    const uint8_t* blk = blk0 + sb * 144;
    const float d = f16(blk);
    const float dmin = f16(blk + 2);
    uint8_t sc[8], mn[8];
    scale_min_k4(blk + 4, sc, mn);
    for (int j = 0; j < 8; j++) {
      const uint16_t es = bf16_rne(d * static_cast<float>(sc[j]));
      const uint16_t em = bf16_rne(dmin * static_cast<float>(mn[j]));
      for (int l = sb * 8 + j; l < 64; l += S) {
        smt[l] = es;
        smt[64 + l] = em;
      }
    }
    const uint8_t* fq = blk + 16;  // 128 nibble bytes: g*32+i
    for (int subp = 0; subp < 4; subp++) {     // sub-block pairs 2g/2g+1
      const uint8_t* q = fq + 32 * subp;
      const int s_even = sb * 8 + 2 * subp;
      const int s_odd = s_even + 1;
      for (int e = 0; e < 16; e++) {
        const int lo_e = q[e] & 0x0F, lo_h = q[e + 16] & 0x0F;
        const int hi_e = q[e] >> 4, hi_h = q[e + 16] >> 4;
        // byte index e*S + s pairs nib(s,e) with nib(s,e+16)
        qt[e * S + s_even] = static_cast<int8_t>(((lo_h - 8) << 4) + lo_e);
        qt[e * S + s_odd] = static_cast<int8_t>(((hi_h - 8) << 4) + hi_e);
      }
    }
  }
}

// Q4_K: src blocks (row-major, 144 B each) -> qs (n, kw/2) int8 + sm
// (kw/2048, n, 128) bf16 over the kw columns in whole tiles, and the tail's
// qs_t (n, tail/2) + sm_t (1, n, 128).
void prep_q4k_row(const uint8_t* src, int64_t n_out, int64_t k_in, int64_t row,
                  int8_t* qs, uint16_t* sm, int8_t* qs_t, uint16_t* sm_t) {
  const int64_t nb = k_in / QK_K;
  const int64_t kt = k_in / TKQ;
  const int64_t tail = k_in % TKQ;
  const uint8_t* rb = src + row * nb * 144;
  int8_t* qrow = qs + row * (kt * TKQ / 2);
  for (int64_t t = 0; t < kt; t++)
    prep_q4k_tile(rb + t * 8 * 144, 8, qrow + t * (TKQ / 2),
                  sm + (t * n_out + row) * 128);
  if (tail)
    prep_q4k_tile(rb + kt * 8 * 144, static_cast<int>(tail / QK_K),
                  qs_t + row * (tail / 2), sm_t + row * 128);
}

// One Q6_K tile of `nsb` super-blocks -> q4t (nsb*128 bytes), q2t (nsb*64),
// smt (128 bf16: the S = nsb*16 sub-scales tiled to 128 lanes).  Tile
// columns c = e*S + s (s = sub-block of 16); q4 byte b = e*S+s (e<8) packs
// nib(s,e),nib(s,e+8); q2 byte b = e'*S+s (e'<4) packs crumbs of elements
// e', e'+4, e'+8, e'+12.
void prep_q6k_tile(const uint8_t* blk0, int nsb, int8_t* q4t, int8_t* q2t,
                   uint16_t* smt) {
  const int S = nsb * 16;
  uint8_t q6[256];
  for (int sb = 0; sb < nsb; sb++) {
    const uint8_t* blk = blk0 + sb * 210;
    const int8_t* scales = reinterpret_cast<const int8_t*>(blk + 192);
    const float d = f16(blk + 208);
    for (int half = 0; half < 2; half++) {
      const uint8_t* ql = blk + 64 * half;
      const uint8_t* qh = blk + 128 + 32 * half;
      uint8_t* q6h = q6 + 128 * half;
      for (int l = 0; l < 128; l++) {
        const int low = (l < 64) ? (ql[l] & 0x0F) : (ql[l - 64] >> 4);
        const int high = (qh[l & 31] >> (2 * (l >> 5))) & 3;
        q6h[l] = static_cast<uint8_t>(low | (high << 4));
      }
    }
    for (int sub = 0; sub < 16; sub++) {
      const int s = sb * 16 + sub;  // tile-local sub-block column
      const uint16_t eff = bf16_rne(d * static_cast<float>(scales[sub]));
      for (int l = s; l < 128; l += S) smt[l] = eff;
      const uint8_t* qe = q6 + sub * 16;  // elements of this sub-block
      for (int e = 0; e < 8; e++) {
        const int nib_lo = qe[e] & 0x0F;
        const int nib_hi = qe[e + 8] & 0x0F;
        q4t[e * S + s] = static_cast<int8_t>(((nib_hi - 8) << 4) + nib_lo);
      }
      for (int ep = 0; ep < 4; ep++) {
        const int c0 = qe[ep] >> 4;
        const int c1 = qe[ep + 4] >> 4;
        const int c2 = qe[ep + 8] >> 4;
        const int c3 = qe[ep + 12] >> 4;
        q2t[ep * S + s] = static_cast<int8_t>(
            (((c3 * 4 + c2) * 4 + c1) * 4 + c0) - 128);
      }
    }
  }
}

// Q6_K: src blocks (210 B) -> q4 (n, kw/2) int8 + q2 (n, kw/4) int8 + sm6
// (kw/2048, n, 128) bf16 over the whole tiles, and the tail's q4_t (n,
// tail/2) + q2_t (n, tail/4) + sm6_t (1, n, 128).
void prep_q6k_row(const uint8_t* src, int64_t n_out, int64_t k_in, int64_t row,
                  int8_t* q4, int8_t* q2, uint16_t* sm6, int8_t* q4_t,
                  int8_t* q2_t, uint16_t* sm6_t) {
  const int64_t nb = k_in / QK_K;
  const int64_t kt = k_in / TKQ;
  const int64_t tail = k_in % TKQ;
  const uint8_t* rb = src + row * nb * 210;
  int8_t* q4row = q4 + row * (kt * TKQ / 2);
  int8_t* q2row = q2 + row * (kt * TKQ / 4);
  for (int64_t t = 0; t < kt; t++)
    prep_q6k_tile(rb + t * 8 * 210, 8, q4row + t * (TKQ / 2),
                  q2row + t * (TKQ / 4), sm6 + (t * n_out + row) * 128);
  if (tail)
    prep_q6k_tile(rb + kt * 8 * 210, static_cast<int>(tail / QK_K),
                  q4_t + row * (tail / 2), q2_t + row * (tail / 4),
                  sm6_t + row * 128);
}

// Q5_K: src blocks (176 B) -> q5s (n, k/2) int8 (Q4_K qs layout of the low
// nibbles) + q5h (n, k/8) int8 (hi-bit bytes: tile byte b packs bit j of
// columns j*256+b, biased -128) + sm5 (k/2048, n, 128) bf16.
void prep_q5k_row(const uint8_t* src, int64_t n_out, int64_t k_in, int64_t row,
                  int8_t* q5s, int8_t* q5h, uint16_t* sm5) {
  const int64_t nb = k_in / QK_K;
  const int64_t kt = k_in / TKQ;
  const uint8_t* rb = src + row * nb * 176;
  int8_t* qsrow = q5s + row * (k_in / 2);
  int8_t* qhrow = q5h + row * (k_in / 8);
  uint8_t nib[2048], hb[2048];
  for (int64_t t = 0; t < kt; t++) {
    uint16_t* smt = sm5 + (t * n_out + row) * 128;
    for (int sb = 0; sb < 8; sb++) {
      const uint8_t* blk = rb + (t * 8 + sb) * 176;
      const float d = f16(blk);
      const float dmin = f16(blk + 2);
      uint8_t sc[8], mn[8];
      scale_min_k4(blk + 4, sc, mn);
      for (int j = 0; j < 8; j++) {
        smt[sb * 8 + j] = bf16_rne(d * static_cast<float>(sc[j]));
        smt[64 + sb * 8 + j] = bf16_rne(dmin * static_cast<float>(mn[j]));
      }
      const uint8_t* qh = blk + 16;
      const uint8_t* fq = blk + 48;
      for (int sub = 0; sub < 8; sub++) {
        const int s = sb * 8 + sub;
        const uint8_t* q = fq + (sub / 2) * 32;
        for (int e = 0; e < 32; e++) {
          const int c = e * 64 + s;
          nib[c] = (sub & 1) ? (q[e] >> 4) : (q[e] & 0x0F);
          hb[c] = (qh[e] >> sub) & 1;
        }
      }
    }
    int8_t* qst = qsrow + t * (TKQ / 2);
    for (int e = 0; e < 16; e++)
      for (int s = 0; s < 64; s++)
        qst[e * 64 + s] = static_cast<int8_t>(
            ((static_cast<int>(nib[(e + 16) * 64 + s]) - 8) << 4) +
            nib[e * 64 + s]);
    int8_t* qht = qhrow + t * (TKQ / 8);
    for (int b = 0; b < 256; b++) {
      int v = 0;
      for (int j = 0; j < 8; j++) v |= static_cast<int>(hb[j * 256 + b]) << j;
      qht[b] = static_cast<int8_t>(v - 128);
    }
  }
}

// Q8_0: src blocks (34 B = f16 d | 32 x i8) -> q8 (n, k) int8 element-major
// tile columns (column c = e*64 + b) + sm8 (k/2048, n, 128) bf16 [d|d].
void prep_q8_0_row(const uint8_t* src, int64_t n_out, int64_t k_in,
                   int64_t row, int8_t* q8, uint16_t* sm8) {
  const int64_t nb = k_in / 32;
  const int64_t kt = k_in / TKQ;
  const uint8_t* rb = src + row * nb * 34;
  int8_t* qrow = q8 + row * k_in;
  for (int64_t t = 0; t < kt; t++) {
    uint16_t* smt = sm8 + (t * n_out + row) * 128;
    int8_t* qt = qrow + t * TKQ;
    for (int b = 0; b < 64; b++) {
      const uint8_t* blk = rb + (t * 64 + b) * 34;
      const uint16_t ds = bf16_rne(f16(blk));
      smt[b] = ds;
      smt[64 + b] = ds;
      const int8_t* q = reinterpret_cast<const int8_t*>(blk + 2);
      for (int e = 0; e < 32; e++) qt[e * 64 + b] = q[e];
    }
  }
}

// Whether the packers lay `k_in` out: whole 2048 tiles, or at least one and
// a tail tile of 512 or 1024 columns whose planes (`all_given`) are there
// (which K is STORED so is ops/pallas/qmatmul.py tail_of's to say).
bool tail_ok(int64_t k_in, bool all_given) {
  const int64_t tail = k_in % TKQ;
  return tail == 0 ||
         (k_in > TKQ && (tail == 512 || tail == 1024) && all_given);
}

}  // namespace

extern "C" {

int lfkt_supported(int ggml_type) {
  return (ggml_type == T_F32 || ggml_type == T_F16 || ggml_type == T_BF16 ||
          find_fmt(ggml_type) != nullptr)
             ? 1
             : 0;
}

// Fused-layout packers.  rc: 0 ok, -2 bad args.  The `_t` planes are the
// tail tile's (unused, and may be null, where 2048 divides k_in).
int lfkt_prep_q4k(const uint8_t* src, int64_t n_out, int64_t k_in,
                  int8_t* qs, uint16_t* sm, int8_t* qs_t, uint16_t* sm_t,
                  int n_threads) {
  if (!src || !qs || !sm || n_out <= 0 || k_in <= 0 ||
      !tail_ok(k_in, qs_t && sm_t))
    return -2;
  if (n_threads <= 0)
    n_threads = static_cast<int>(std::thread::hardware_concurrency());
  if (n_threads <= 0) n_threads = 1;
  run_threads(n_out, n_threads, [&](int64_t lo, int64_t hi) {
    for (int64_t r = lo; r < hi; r++)
      prep_q4k_row(src, n_out, k_in, r, qs, sm, qs_t, sm_t);
  });
  return 0;
}

int lfkt_prep_q6k(const uint8_t* src, int64_t n_out, int64_t k_in,
                  int8_t* q4, int8_t* q2, uint16_t* sm6, int8_t* q4_t,
                  int8_t* q2_t, uint16_t* sm6_t, int n_threads) {
  if (!src || !q4 || !q2 || !sm6 || n_out <= 0 || k_in <= 0 ||
      !tail_ok(k_in, q4_t && q2_t && sm6_t))
    return -2;
  if (n_threads <= 0)
    n_threads = static_cast<int>(std::thread::hardware_concurrency());
  if (n_threads <= 0) n_threads = 1;
  run_threads(n_out, n_threads, [&](int64_t lo, int64_t hi) {
    for (int64_t r = lo; r < hi; r++)
      prep_q6k_row(src, n_out, k_in, r, q4, q2, sm6, q4_t, q2_t, sm6_t);
  });
  return 0;
}

int lfkt_prep_q5k(const uint8_t* src, int64_t n_out, int64_t k_in,
                  int8_t* q5s, int8_t* q5h, uint16_t* sm5, int n_threads) {
  if (!src || !q5s || !q5h || !sm5 || n_out <= 0 || k_in <= 0 ||
      k_in % TKQ != 0)
    return -2;
  if (n_threads <= 0)
    n_threads = static_cast<int>(std::thread::hardware_concurrency());
  if (n_threads <= 0) n_threads = 1;
  run_threads(n_out, n_threads, [&](int64_t lo, int64_t hi) {
    for (int64_t r = lo; r < hi; r++)
      prep_q5k_row(src, n_out, k_in, r, q5s, q5h, sm5);
  });
  return 0;
}

int lfkt_prep_q8_0(const uint8_t* src, int64_t n_out, int64_t k_in,
                   int8_t* q8, uint16_t* sm8, int n_threads) {
  if (!src || !q8 || !sm8 || n_out <= 0 || k_in <= 0 || k_in % TKQ != 0)
    return -2;
  if (n_threads <= 0)
    n_threads = static_cast<int>(std::thread::hardware_concurrency());
  if (n_threads <= 0) n_threads = 1;
  run_threads(n_out, n_threads, [&](int64_t lo, int64_t hi) {
    for (int64_t r = lo; r < hi; r++)
      prep_q8_0_row(src, n_out, k_in, r, q8, sm8);
  });
  return 0;
}

int lfkt_dequant(int ggml_type, const uint8_t* src, int64_t n_elements,
                 float* dst, int n_threads) {
  if (!src || !dst || n_elements < 0) return -2;
  if (n_threads <= 0)
    n_threads = static_cast<int>(std::thread::hardware_concurrency());
  if (n_threads <= 0) n_threads = 1;

  switch (ggml_type) {
    case T_F32:
      run_threads(n_elements, n_threads, [&](int64_t lo, int64_t hi) {
        conv_range_f32(src, dst, lo, hi);
      });
      return 0;
    case T_F16:
      run_threads(n_elements, n_threads, [&](int64_t lo, int64_t hi) {
        conv_range_f16(src, dst, lo, hi);
      });
      return 0;
    case T_BF16:
      run_threads(n_elements, n_threads, [&](int64_t lo, int64_t hi) {
        conv_range_bf16(src, dst, lo, hi);
      });
      return 0;
    default:
      break;
  }

  const Fmt* fmt = find_fmt(ggml_type);
  if (!fmt) return -1;
  if (n_elements % fmt->block_elems != 0) return -2;
  const int64_t n_blocks = n_elements / fmt->block_elems;
  run_threads(n_blocks, n_threads, [&](int64_t lo, int64_t hi) {
    for (int64_t blk = lo; blk < hi; blk++) {
      fmt->fn(src + blk * fmt->block_bytes, dst + blk * fmt->block_elems);
    }
  });
  return 0;
}

}  // extern "C"
