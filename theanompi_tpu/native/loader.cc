// Native data-loader hot path.
//
// TPU-native rebuild of the runtime-native part of Theano-MPI's parallel
// loader (reference: theanompi/models/data/ loader child process +
// lib/exchanger_strategy.py PyCUDA kernels — SURVEY.md §2.8, §2.9 N3):
// the reference spawned a child process that loaded a .hkl batch, ran
// crop/mirror/mean-subtract augmentation on CPU, and wrote the float32
// result into the trainer's GPU buffer over a CUDA IPC handle.  On TPU the
// IPC trick is ordinary async host→device transfer, and the arithmetic
// (cast, mean-subtract) belongs to the step program
// (ModelBase.stage_input): what is left to the host is the gather — crop
// window and mirror — on uint8, a quarter of the bytes a float32 batch
// would cost to write, lay out for the device and send.  NumPy does the
// gather single-threaded, image by image under the GIL; this library does
// it in one multithreaded pass.
//
// Exposed as a plain C ABI consumed via ctypes (no pybind11 in this
// environment).  Output is always NHWC uint8 (TPU conv layout); input may
// be NHWC or NCHW ("bc01", the reference's batch-file layout) — the
// transpose fuses into the same pass.
//
// Build: g++ -O3 -shared -fPIC -pthread loader.cc -o _loader-<hash>.so
// (driven by theanompi_tpu/native/__init__.py, keyed on source and flags).

#include <cstdint>
#include <cstring>
#include <thread>
#include <vector>

namespace {

struct AugmentArgs {
  const uint8_t* in;   // [n,h,w,c] or [n,c,h,w]
  uint8_t* out;        // [n,crop,crop,c]
  int n, h, w, c, crop;
  int in_nchw;         // input layout: 0 = NHWC, 1 = NCHW
  const int* oy;       // per-image crop offsets [n]
  const int* ox;       // [n]
  const uint8_t* flip; // per-image horizontal mirror [n]
};

// One image: crop + mirror (+ transpose), bytes in, bytes out.
void augment_one(const AugmentArgs& a, int i) {
  const int h = a.h, w = a.w, c = a.c, crop = a.crop;
  const int oy = a.oy[i], ox = a.ox[i];
  const bool flip = a.flip[i] != 0;
  uint8_t* dst = a.out + (size_t)i * crop * crop * c;

  if (!a.in_nchw) {
    const uint8_t* src = a.in + (size_t)i * h * w * c;
    for (int y = 0; y < crop; ++y) {
      const uint8_t* row = src + ((size_t)(y + oy) * w + ox) * c;
      uint8_t* drow = dst + (size_t)y * crop * c;
      if (!flip) {
        std::memcpy(drow, row, (size_t)crop * c);
      } else {
        // mirror: output x reads input (crop-1-x)
        for (int x = 0; x < crop; ++x) {
          const uint8_t* px = row + (size_t)(crop - 1 - x) * c;
          uint8_t* dpx = drow + (size_t)x * c;
          for (int k = 0; k < c; ++k) dpx[k] = px[k];
        }
      }
    }
  } else {
    // NCHW input: gather channel planes, write NHWC.
    const uint8_t* src = a.in + (size_t)i * c * h * w;
    for (int y = 0; y < crop; ++y) {
      uint8_t* drow = dst + (size_t)y * crop * c;
      for (int x = 0; x < crop; ++x) {
        const int sx = flip ? (ox + crop - 1 - x) : (ox + x);
        const size_t plane_off = (size_t)(y + oy) * w + sx;
        uint8_t* dpx = drow + (size_t)x * c;
        for (int k = 0; k < c; ++k) dpx[k] = src[(size_t)k * h * w + plane_off];
      }
    }
  }
}

void run_range(const AugmentArgs& a, int lo, int hi) {
  for (int i = lo; i < hi; ++i) augment_one(a, i);
}

}  // namespace

extern "C" {

// Batch crop + mirror.  in: uint8 [n,h,w,c] (in_nchw=0) or [n,c,h,w]
// (in_nchw=1); out: uint8 [n,crop,crop,c]; oy/ox/flip: per-image params
// [n].  n_threads<=1 runs inline.
void tmpi_augment_u8(const uint8_t* in, uint8_t* out, int n, int h, int w,
                     int c, int crop, int in_nchw, const int* oy,
                     const int* ox, const uint8_t* flip, int n_threads) {
  AugmentArgs a{in, out, n, h, w, c, crop, in_nchw, oy, ox, flip};
  if (n_threads <= 1 || n <= 1) {
    run_range(a, 0, n);
    return;
  }
  if (n_threads > n) n_threads = n;
  std::vector<std::thread> ts;
  ts.reserve(n_threads);
  const int per = (n + n_threads - 1) / n_threads;
  for (int t = 0; t < n_threads; ++t) {
    const int lo = t * per;
    const int hi = lo + per < n ? lo + per : n;
    if (lo >= hi) break;
    ts.emplace_back([&a, lo, hi] { run_range(a, lo, hi); });
  }
  for (auto& t : ts) t.join();
}

// Version stamp so the Python side can cache-bust compiled objects.
int tmpi_loader_abi_version() { return 2; }

}  // extern "C"
