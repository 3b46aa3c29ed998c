// Native host-side image ingest: JPEG decode -> RGB -> antialiased bilinear
// resize -> ImageNet normalize, batched over an internal thread pool. The
// output's element type is the caller's (float32 or bfloat16): the normalize
// pass, the last one over an image's pixels, stores it, so no thread of the
// caller converts a batch afterwards.
//
// Why this exists (capability parity, done TPU-host-native): the reference
// hides Python-side decode cost behind torch DataLoader worker *processes*
// (data_loader.py:29-39) and, for inference, behind three dedicated MPI
// preprocessing ranks (evaluation_pipeline.py:53-129). Both are native-code
// strategies in disguise — torch workers and libmpi are C/C++. This library
// is the equivalent for the TPU host: one ctypes call per batch decodes every
// image on C++ threads with the GIL released, so Python never serializes the
// ingest path. libjpeg DCT prescaling (scale_num/8) decodes large sources
// directly to ~target resolution, skipping IDCT work PIL would do at full res.
//
// The resize is the same algorithm Pillow uses for Image.resize(BILINEAR)
// since 2.7 (separable triangle filter with antialiasing support scaled by
// the downscale factor), computed in float32 instead of Pillow's 8.22 fixed
// point — outputs match PIL within ~1/255 per pixel (asserted by
// tests/test_native_decode.py).
//
// C ABI only (no pybind11 in this image); consumed via ctypes from
// mpi_pytorch_tpu/native/__init__.py.

#include <cstddef>  // jpeglib.h uses size_t/FILE without including them
#include <cstdio>

#include <jpeglib.h>

#include <atomic>
#include <chrono>
#include <cmath>
#include <csetjmp>
#include <cstdint>
#include <cstdio>
#include <cstring>
#include <thread>
#include <vector>

namespace {

// ---------------------------------------------------------------------------
// libjpeg error trampoline: convert fatal decode errors into a longjmp so a
// corrupt file fails one item, not the process.
// ---------------------------------------------------------------------------
struct ErrMgr {
  jpeg_error_mgr pub;
  jmp_buf jb;
};

void on_error_exit(j_common_ptr cinfo) {
  ErrMgr* e = reinterpret_cast<ErrMgr*>(cinfo->err);
  longjmp(e->jb, 1);
}

void on_output_message(j_common_ptr) {}  // swallow warnings

// ---------------------------------------------------------------------------
// Where one image's time goes: the laps of decode_file in the order they
// run, timed on the worker thread by ONE clock read at each boundary. The
// laps partition the worker's busy time (first read to last: the busy time
// IS their sum); a refused or failed image leaves what it spent on the lap
// it stopped in. Reported as four stages — file, jpeg (= head +
// scan), resize, normalize — and jpeg's scanline loop beside them, the one
// stage that is over half of an image.
// ---------------------------------------------------------------------------
using Clock = std::chrono::steady_clock;

enum Lap {
  LAP_FILE = 0,   // fopen .. fclose: the encoded bytes into memory
  LAP_JPEG_HEAD,  // jpeg_create_decompress, the header, jpeg_start_decompress
  LAP_JPEG_SCAN,  // the scanline loop (entropy decode, scaled IDCT, colour
                  // conversion) .. jpeg_destroy_decompress
  LAP_RESIZE,     // resize_rgb with its two make_kernel calls (or the
                  // equal-size uint8 -> float copy), into the worker's scratch
  LAP_NORMALIZE,  // the scale-and-shift pass, scratch -> the batch row in the
                  // output's element type
  N_LAPS,
};

struct LapClock {
  Clock::time_point last = Clock::now();
  int lap = LAP_FILE;
  long long ns[N_LAPS] = {};

  // The lap in progress ends here; `next` begins.
  void enter(int next) {
    const Clock::time_point now = Clock::now();
    ns[lap] +=
        std::chrono::duration_cast<std::chrono::nanoseconds>(now - last).count();
    last = now;
    lap = next;
  }
};

// ---------------------------------------------------------------------------
// Separable antialiased triangle-filter resize (Pillow's BILINEAR).
// ---------------------------------------------------------------------------
struct ResampleKernel {
  int ksize = 0;
  std::vector<int> xmin;     // first source index per output coord
  std::vector<int> count;    // taps per output coord
  std::vector<float> coeff;  // [out_size * ksize] normalized weights
};

ResampleKernel make_kernel(int in_size, int out_size) {
  ResampleKernel k;
  const double scale = static_cast<double>(in_size) / out_size;
  const double filterscale = scale < 1.0 ? 1.0 : scale;
  const double support = 1.0 * filterscale;  // triangle filter support = 1
  k.ksize = static_cast<int>(std::ceil(support)) * 2 + 1;
  k.xmin.resize(out_size);
  k.count.resize(out_size);
  k.coeff.assign(static_cast<size_t>(out_size) * k.ksize, 0.0f);
  const double ss = 1.0 / filterscale;
  for (int xx = 0; xx < out_size; ++xx) {
    const double center = (xx + 0.5) * scale;
    int xmin = static_cast<int>(center - support + 0.5);
    if (xmin < 0) xmin = 0;
    int xmax = static_cast<int>(center + support + 0.5);
    if (xmax > in_size) xmax = in_size;
    const int count = xmax - xmin;
    float* w = &k.coeff[static_cast<size_t>(xx) * k.ksize];
    double total = 0.0;
    for (int x = 0; x < count; ++x) {
      const double arg = (x + xmin - center + 0.5) * ss;
      const double v = std::abs(arg) < 1.0 ? 1.0 - std::abs(arg) : 0.0;
      w[x] = static_cast<float>(v);
      total += v;
    }
    if (total != 0.0) {
      for (int x = 0; x < count; ++x) w[x] = static_cast<float>(w[x] / total);
    }
    k.xmin[xx] = xmin;
    k.count[xx] = count;
  }
  return k;
}

// uint8 RGB [in_h, in_w, 3] -> float32 RGB [out_h, out_w, 3], values in [0,255].
void resize_rgb(const uint8_t* src, int in_h, int in_w, float* dst, int out_h,
                int out_w, std::vector<float>& scratch) {
  const ResampleKernel kh = make_kernel(in_w, out_w);
  const ResampleKernel kv = make_kernel(in_h, out_h);
  // Horizontal pass: [in_h, in_w, 3] -> scratch [in_h, out_w, 3]
  scratch.resize(static_cast<size_t>(in_h) * out_w * 3);
  for (int y = 0; y < in_h; ++y) {
    const uint8_t* row = src + static_cast<size_t>(y) * in_w * 3;
    float* orow = scratch.data() + static_cast<size_t>(y) * out_w * 3;
    for (int xx = 0; xx < out_w; ++xx) {
      const float* w = &kh.coeff[static_cast<size_t>(xx) * kh.ksize];
      const int xmin = kh.xmin[xx];
      const int count = kh.count[xx];
      float r = 0.f, g = 0.f, b = 0.f;
      for (int t = 0; t < count; ++t) {
        const uint8_t* p = row + static_cast<size_t>(xmin + t) * 3;
        r += w[t] * p[0];
        g += w[t] * p[1];
        b += w[t] * p[2];
      }
      orow[xx * 3 + 0] = r;
      orow[xx * 3 + 1] = g;
      orow[xx * 3 + 2] = b;
    }
  }
  // Vertical pass: scratch [in_h, out_w, 3] -> dst [out_h, out_w, 3]
  for (int yy = 0; yy < out_h; ++yy) {
    const float* w = &kv.coeff[static_cast<size_t>(yy) * kv.ksize];
    const int ymin = kv.xmin[yy];
    const int count = kv.count[yy];
    float* orow = dst + static_cast<size_t>(yy) * out_w * 3;
    std::memset(orow, 0, sizeof(float) * out_w * 3);
    for (int t = 0; t < count; ++t) {
      const float* irow = scratch.data() + static_cast<size_t>(ymin + t) * out_w * 3;
      const float wt = w[t];
      for (int i = 0; i < out_w * 3; ++i) orow[i] += wt * irow[i];
    }
  }
}

// ---------------------------------------------------------------------------
// The output's element type (mirrored in native/__init__.py) and its store.
// ---------------------------------------------------------------------------
enum Elem {
  ELEM_F32 = 0,
  ELEM_BF16 = 1,  // the upper half of a float32, as ml_dtypes.bfloat16
};

// Bytes of one element; 0 for a type the decoder does not write.
size_t elem_size(int elem) {
  return elem == ELEM_F32 ? 4 : elem == ELEM_BF16 ? 2 : 0;
}

inline void store(float v, float* dst) { *dst = v; }

// float32 -> bfloat16, rounded to nearest with ties to even: the rounding
// ml_dtypes' astype does, so a batch stored here has the bits of a float32
// batch converted afterwards. Normalized pixels are finite and small (about
// -2.2 .. 2.7), so neither the NaN case nor an overflow of the add arises.
inline void store(float v, uint16_t* dst) {
  uint32_t bits;
  std::memcpy(&bits, &v, sizeof bits);
  bits += 0x7FFFu + ((bits >> 16) & 1u);
  *dst = static_cast<uint16_t>(bits >> 16);
}

// [0,255] -> ([0,1] - mean) / std over n_pixels RGB pixels, stored as T.
template <typename T>
void normalize_store(const float* src, int n_pixels, const float* mean,
                     const float* stdv, T* dst) {
  const float inv255 = 1.0f / 255.0f;
  float scale[3], shift[3];
  for (int c = 0; c < 3; ++c) {
    scale[c] = inv255 / stdv[c];
    shift[c] = -mean[c] / stdv[c];
  }
  for (int i = 0; i < n_pixels; ++i, src += 3, dst += 3) {
    store(src[0] * scale[0] + shift[0], dst + 0);
    store(src[1] * scale[1] + shift[1], dst + 1);
    store(src[2] * scale[2] + shift[2], dst + 2);
  }
}

void normalize_store(const float* src, int n_pixels, const float* mean,
                     const float* stdv, void* dst, int elem) {
  if (elem == ELEM_BF16) {
    normalize_store(src, n_pixels, mean, stdv, static_cast<uint16_t*>(dst));
  } else {
    normalize_store(src, n_pixels, mean, stdv, static_cast<float*>(dst));
  }
}

// Status codes returned per item (mirrored in native/__init__.py).
enum Status {
  OK = 0,
  ERR_OPEN = 1,    // file unreadable
  ERR_DECODE = 2,  // libjpeg failed (corrupt / not a JPEG)
  ERR_FORMAT = 3,  // colorspace we refuse (e.g. CMYK) -> caller falls back
};

// Per-worker buffers, reused from image to image.
struct Scratch {
  std::vector<uint8_t> filebuf;  // the encoded file
  std::vector<uint8_t> pixels;   // libjpeg's RGB output
  std::vector<float> hpass;      // the resize's horizontal pass
  std::vector<float> resized;    // [out_h, out_w, 3] in [0,255]: 196 KB at
                                 // 128 px, so the normalize pass reads cache
};

int decode_buffer(const uint8_t* buf, size_t len, int out_h, int out_w,
                  const float* mean, const float* stdv, void* out, int elem,
                  int prescale_margin, Scratch& scratch, LapClock& clock) {
  std::vector<uint8_t>& pixels = scratch.pixels;
  clock.enter(LAP_JPEG_HEAD);
  jpeg_decompress_struct cinfo;
  ErrMgr err;
  cinfo.err = jpeg_std_error(&err.pub);
  err.pub.error_exit = on_error_exit;
  err.pub.output_message = on_output_message;
  if (setjmp(err.jb)) {
    jpeg_destroy_decompress(&cinfo);
    return ERR_DECODE;
  }
  jpeg_create_decompress(&cinfo);
  jpeg_mem_src(&cinfo, const_cast<uint8_t*>(buf), len);
  jpeg_read_header(&cinfo, TRUE);

  if (cinfo.jpeg_color_space == JCS_CMYK || cinfo.jpeg_color_space == JCS_YCCK) {
    jpeg_destroy_decompress(&cinfo);
    return ERR_FORMAT;  // rare; Python side falls back to PIL
  }
  cinfo.out_color_space = JCS_RGB;  // libjpeg expands grayscale/YCbCr to RGB

  // DCT prescale: decode at a num/8 scale, skipping full-resolution IDCT for
  // large sources; the antialiased resize below does the final ratio.
  // prescale_margin = how many times the target the scaled decode must still
  // cover: 0 disables prescale (bit-parity with PIL's full decode), 1 decodes
  // just past the target (fastest, strongest low-pass deviation), 2 keeps a
  // 2x margin so every frequency the final triangle filter passes survives
  // the scaled IDCT (near-PIL output at most of the speedup).
  // Only power-of-two scales: libjpeg's 8/8, 4/8, 2/8, 1/8 IDCTs are the
  // optimized paths — intermediate scales (e.g. 6/8) use the general scaled
  // DCT and measure SLOWER than a full decode (3.6 vs 3.4 ms/img on a 350px
  // source; see tests/test_native_decode.py's bench note).
  if (prescale_margin > 0) {
    const unsigned full_w = cinfo.image_width, full_h = cinfo.image_height;
    const unsigned need_w = static_cast<unsigned>(out_w) * prescale_margin;
    const unsigned need_h = static_cast<unsigned>(out_h) * prescale_margin;
    unsigned num = 8;
    while (num > 1 && (full_w * (num / 2)) / 8 >= need_w &&
           (full_h * (num / 2)) / 8 >= need_h) {
      num /= 2;
    }
    cinfo.scale_num = num;
    cinfo.scale_denom = 8;
  }

  jpeg_start_decompress(&cinfo);
  const int w = cinfo.output_width, h = cinfo.output_height;
  const int comps = cinfo.output_components;
  if (comps != 3) {  // out_color_space=JCS_RGB should guarantee 3
    jpeg_abort_decompress(&cinfo);
    jpeg_destroy_decompress(&cinfo);
    return ERR_FORMAT;
  }
  pixels.resize(static_cast<size_t>(w) * h * 3);
  clock.enter(LAP_JPEG_SCAN);
  while (cinfo.output_scanline < cinfo.output_height) {
    JSAMPROW row = pixels.data() + static_cast<size_t>(cinfo.output_scanline) * w * 3;
    jpeg_read_scanlines(&cinfo, &row, 1);
  }
  jpeg_finish_decompress(&cinfo);
  jpeg_destroy_decompress(&cinfo);

  clock.enter(LAP_RESIZE);
  const size_t n_values = static_cast<size_t>(out_h) * out_w * 3;
  scratch.resized.resize(n_values);
  float* resized = scratch.resized.data();
  if (w == out_w && h == out_h) {
    for (size_t i = 0; i < n_values; ++i) {
      resized[i] = static_cast<float>(pixels[i]);
    }
  } else {
    resize_rgb(pixels.data(), h, w, resized, out_h, out_w, scratch.hpass);
  }
  // Normalize and store the caller's element type, fused here so Python never
  // touches pixels.
  clock.enter(LAP_NORMALIZE);
  normalize_store(resized, out_h * out_w, mean, stdv, out, elem);
  return OK;
}

int decode_file(const char* path, int out_h, int out_w, const float* mean,
                const float* stdv, void* out, int elem, int prescale_margin,
                Scratch& scratch, LapClock& clock) {
  std::vector<uint8_t>& filebuf = scratch.filebuf;
  FILE* f = std::fopen(path, "rb");
  if (!f) return ERR_OPEN;
  std::fseek(f, 0, SEEK_END);
  const long sz = std::ftell(f);
  std::fseek(f, 0, SEEK_SET);
  if (sz <= 0) {
    std::fclose(f);
    return ERR_OPEN;
  }
  filebuf.resize(static_cast<size_t>(sz));
  const size_t got = std::fread(filebuf.data(), 1, filebuf.size(), f);
  std::fclose(f);
  if (got != filebuf.size()) return ERR_OPEN;
  return decode_buffer(filebuf.data(), filebuf.size(), out_h, out_w, mean, stdv,
                       out, elem, prescale_margin, scratch, clock);
}

// Process-wide counters of what the batch entry point's worker threads did,
// read by the loader before and after a call (the difference is that call's
// share) and written on its `loader/decode` span (data/pipeline.py): images
// refused (left to the caller's fallback) -> `fallbacks`; nanoseconds the
// workers spent inside a decode -> `thread_busy_s` (busy / (threads x wall)
// says whether N workers were N cores' worth of work); and those nanoseconds
// by lap -> `stage_s` (which of file, jpeg, resize, normalize an image's
// milliseconds go to) and `jpeg_scan_s` (how much of jpeg is its scanline
// loop). Always on: six clock reads and five relaxed adds an image (~0.15 us)
// against milliseconds of decode.
std::atomic<long long> g_refused(0);
std::atomic<long long> g_lap_ns[N_LAPS];  // zero-initialized (static storage)

}  // namespace

extern "C" {

// Decode one in-memory JPEG into out[out_h*out_w*3] (normalized HWC of
// out_elem, an Elem).
int mpt_decode_one(const uint8_t* buf, size_t len, int out_h, int out_w,
                   const float* mean, const float* stdv, void* out,
                   int out_elem, int prescale_margin) {
  if (elem_size(out_elem) == 0) return ERR_FORMAT;
  try {
    Scratch scratch;
    LapClock clock;  // not counted: the counters are the batch entry point's
    return decode_buffer(buf, len, out_h, out_w, mean, stdv, out, out_elem,
                         prescale_margin, scratch, clock);
  } catch (...) {
    return ERR_DECODE;  // allocation failure: per-item error, never a throw
  }
}

// Decode n files into out[n*out_h*out_w*3] of out_elem (an Elem: the stride
// follows it) on n_threads C++ threads. statuses[i] receives a Status per
// item; failed items leave zeros for the caller's PIL fallback. Returns the
// number of failed items, -1 for an element type it does not know. The GIL is
// released for the whole call (ctypes).
int mpt_decode_batch(const char** paths, int n, int out_h, int out_w,
                     const float* mean, const float* stdv, void* out,
                     int out_elem, int n_threads, int prescale_margin,
                     int* statuses) {
  if (elem_size(out_elem) == 0) return -1;
  if (n <= 0) return 0;
  if (n_threads < 1) n_threads = 1;
  if (n_threads > n) n_threads = n;
  const size_t stride =
      static_cast<size_t>(out_h) * out_w * 3 * elem_size(out_elem);  // bytes
  uint8_t* const rows = static_cast<uint8_t*>(out);
  std::atomic<int> next(0);
  std::atomic<int> failures(0);
  auto worker = [&]() {
    Scratch scratch;
    for (;;) {
      const int i = next.fetch_add(1);
      if (i >= n) return;
      int st;
      LapClock clock;
      try {
        st = decode_file(paths[i], out_h, out_w, mean, stdv, rows + stride * i,
                         out_elem, prescale_margin, scratch, clock);
      } catch (...) {
        // e.g. std::bad_alloc from a header declaring absurd dimensions
        // (libjpeg permits up to 65500x65500). The contract is per-item
        // failure, never thread/process death.
        st = ERR_DECODE;
      }
      clock.enter(N_LAPS);  // closes the lap the image ended (or stopped) in
      for (int s = 0; s < N_LAPS; ++s) {
        g_lap_ns[s].fetch_add(clock.ns[s], std::memory_order_relaxed);
      }
      statuses[i] = st;
      if (st != OK) {
        // A failed decode may have partially written its slot; zero it so
        // the documented contract (failed items leave zeros) holds even for
        // callers that skip the per-item fallback. (All-zero bytes are 0.0 in
        // either element type.)
        std::memset(rows + stride * i, 0, stride);
        failures.fetch_add(1);
        g_refused.fetch_add(1, std::memory_order_relaxed);
      }
    }
  };
  if (n_threads == 1) {
    worker();
  } else {
    std::vector<std::thread> pool;
    pool.reserve(n_threads);
    for (int t = 0; t < n_threads; ++t) pool.emplace_back(worker);
    for (auto& t : pool) t.join();
  }
  return failures.load();
}

// out7 = {images refused, nanoseconds inside a decode, then those nanoseconds
// by stage: file, jpeg, resize, normalize (they sum to the second value),
// then the part of jpeg inside its scanline loop}, all since the library was
// loaded, over every mpt_decode_batch call.
void mpt_decode_counters(long long* out7) {
  long long lap[N_LAPS], busy = 0;
  for (int s = 0; s < N_LAPS; ++s) {
    lap[s] = g_lap_ns[s].load(std::memory_order_relaxed);
    busy += lap[s];
  }
  out7[0] = g_refused.load(std::memory_order_relaxed);
  out7[1] = busy;
  out7[2] = lap[LAP_FILE];
  out7[3] = lap[LAP_JPEG_HEAD] + lap[LAP_JPEG_SCAN];
  out7[4] = lap[LAP_RESIZE];
  out7[5] = lap[LAP_NORMALIZE];
  out7[6] = lap[LAP_JPEG_SCAN];
}

// The normalize pass alone, as the decoder runs it: src[n_pixels*3] float32 in
// [0,255] -> dst of out_elem. For tests of the store's rounding.
int mpt_normalize_store(const float* src, int n_pixels, const float* mean,
                        const float* stdv, void* dst, int out_elem) {
  if (elem_size(out_elem) == 0) return -1;
  normalize_store(src, n_pixels, mean, stdv, dst, out_elem);
  return 0;
}

int mpt_abi_version() { return 5; }

}  // extern "C"
