// Async file readahead pool for the weight-streaming host path.
//
// The streaming executor's host loader reads one ~GB-scale layer file per
// shard (per-layer safetensors, the contract of
// /root/reference/prepare_weights.py:43 kept by utils/checkpoint.py). The
// Python-side prefetch thread overlaps *device* upload with compute, but the
// cold-cache disk read itself still serialises with the numpy cast/stack
// work on that thread. This pool warms upcoming files into the page cache
// via posix_fadvise(WILLNEED): the KERNEL schedules the readahead (DMA into
// the page cache) asynchronously, so warming costs ~zero CPU and cannot
// contend with the cast/stack work (a full-pread warm steals the caster's
// only core on a 1-core host).
// Filesystems that ignore fadvise degrade to a no-op, never to contention.
//
// Exposed as a plain C ABI for ctypes (no pybind11 in this environment);
// see flexible_llm_sharding_tpu/utils/native.py for the Python wrapper and
// the pure-Python fallback used when no C++ toolchain is available.

#include <atomic>
#include <condition_variable>
#include <cstring>
#include <fcntl.h>
#include <mutex>
#include <queue>
#include <string>
#include <thread>
#include <unistd.h>
#include <vector>

namespace {

struct Pool {
  std::vector<std::thread> workers;
  std::queue<std::string> jobs;
  std::mutex mu;
  std::condition_variable cv;        // workers wait for jobs
  std::condition_variable idle_cv;   // fp_wait_all waits for drain
  size_t inflight = 0;               // queued + running jobs (under mu)
  bool stop = false;

  explicit Pool(int n_threads) {
    for (int i = 0; i < n_threads; ++i) {
      workers.emplace_back([this] { this->run(); });
    }
  }

  ~Pool() {
    {
      std::lock_guard<std::mutex> lock(mu);
      stop = true;
    }
    cv.notify_all();
    for (auto& t : workers) t.join();
  }

  void submit(std::string path) {
    {
      std::lock_guard<std::mutex> lock(mu);
      jobs.push(std::move(path));
      ++inflight;
    }
    cv.notify_one();
  }

  void wait_all() {
    std::unique_lock<std::mutex> lock(mu);
    idle_cv.wait(lock, [this] { return inflight == 0; });
  }

  void run() {
    for (;;) {
      std::string path;
      {
        std::unique_lock<std::mutex> lock(mu);
        cv.wait(lock, [this] { return stop || !jobs.empty(); });
        if (stop && jobs.empty()) return;
        path = std::move(jobs.front());
        jobs.pop();
      }
      warm(path.c_str());
      {
        std::lock_guard<std::mutex> lock(mu);
        --inflight;
        if (inflight == 0) idle_cv.notify_all();
      }
    }
  }

  static void warm(const char* path) {
    int fd = open(path, O_RDONLY);
    if (fd < 0) return;  // missing file: loader will raise a real error later
#ifdef POSIX_FADV_WILLNEED
    // Async kernel readahead only — NO userspace read loop. A streaming
    // pread forces residency even where fadvise is ignored, but it copies
    // every byte through this thread and was measured SLOWING the cold
    // cast stream 0.66-0.88x on a 1-core host (the caster's core is the
    // one doing the copying). fadvise costs microseconds and overlaps via
    // DMA; where it's a no-op the loader just pays the cold read itself.
    posix_fadvise(fd, 0, 0, POSIX_FADV_WILLNEED);
#endif
    close(fd);
  }
};

}  // namespace

extern "C" {

void* fp_create(int n_threads) {
  if (n_threads < 1) n_threads = 1;
  return new Pool(n_threads);
}

void fp_prefetch(void* handle, const char* path) {
  static_cast<Pool*>(handle)->submit(path);
}

void fp_wait_all(void* handle) { static_cast<Pool*>(handle)->wait_all(); }

void fp_destroy(void* handle) { delete static_cast<Pool*>(handle); }

// Direct bulk read into a caller buffer (ctypes-owned); returns bytes read
// or -1. Used for tests and as a building block for future pinned-buffer IO.
long fp_read_file(const char* path, void* out, long cap) {
  int fd = open(path, O_RDONLY);
  if (fd < 0) return -1;
  long total = 0;
  while (total < cap) {
    ssize_t n = pread(fd, static_cast<char*>(out) + total, cap - total, total);
    if (n < 0) {
      close(fd);
      return -1;
    }
    if (n == 0) break;
    total += n;
  }
  close(fd);
  return total;
}

}  // extern "C"
