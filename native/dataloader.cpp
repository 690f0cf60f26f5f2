// kubedl-tpu native data loader.
//
// The reference delegates all data loading to in-container frameworks; the
// TPU build makes host-side input a framework concern: training steps are
// sub-second, so batch assembly must never appear on the critical path.
// This loader memory-maps a binary token file, samples windows with a
// seeded xorshift PRNG, and keeps a ring of pre-assembled batches filled
// by background threads — the consumer thread only memcpy's. Batches
// enter the ring in the order their windows were drawn, so a seed yields
// one fixed SEQUENCE of batches however the threads are scheduled.
//
// C ABI (consumed via ctypes from kubedl_tpu/data/native.py):
//   void* kdl_loader_open(path, batch, seq, seed, prefetch, token_bytes)
//   int   kdl_loader_next(handle, int32* out)   // blocking; 0 = ok
//   long  kdl_loader_tokens(handle)             // total tokens in file
//   void  kdl_loader_close(handle)
//
// Build: g++ -O3 -shared -fPIC -pthread -o libkdl_data.so dataloader.cpp

#include <atomic>
#include <condition_variable>
#include <cstdint>
#include <cstring>
#include <deque>
#include <mutex>
#include <thread>
#include <vector>

#include <fcntl.h>
#include <sys/mman.h>
#include <sys/stat.h>
#include <unistd.h>

namespace {

struct Batch {
  std::vector<int32_t> data;
};

struct Loader {
  const uint8_t* base = nullptr;
  size_t file_bytes = 0;
  int fd = -1;
  long n_tokens = 0;
  int token_bytes = 4;  // 2 (uint16) or 4 (uint32)
  int batch = 0;
  int seq = 0;
  uint64_t rng = 0;

  std::mutex mu;
  std::condition_variable cv_full, cv_empty, cv_order;
  std::deque<Batch> ring;
  size_t ring_cap = 0;
  size_t in_flight = 0;      // drawn, not yet in the ring (slot reserved)
  uint64_t next_ticket = 0;  // order of drawing
  uint64_t next_push = 0;    // the ticket the ring takes next
  std::vector<std::thread> workers;
  std::atomic<bool> stop{false};

  ~Loader() {
    stop.store(true);
    cv_full.notify_all();
    cv_empty.notify_all();
    cv_order.notify_all();
    for (auto& t : workers)
      if (t.joinable()) t.join();
    if (base) munmap(const_cast<uint8_t*>(base), file_bytes);
    if (fd >= 0) close(fd);
  }

  // xorshift64*: deterministic, one state per loader (workers draw window
  // starts under the lock and take a ticket with them)
  uint64_t next_rand() {
    uint64_t x = rng;
    x ^= x >> 12;
    x ^= x << 25;
    x ^= x >> 27;
    rng = x;
    return x * 0x2545F4914F6CDD1DULL;
  }

  int32_t token_at(long i) const {
    if (token_bytes == 2)
      return reinterpret_cast<const uint16_t*>(base)[i];
    return reinterpret_cast<const int32_t*>(base)[i];
  }

  void fill_batch(Batch& b, const std::vector<long>& starts) {
    b.data.resize(static_cast<size_t>(batch) * seq);
    for (int r = 0; r < batch; ++r) {
      long s = starts[r];
      if (token_bytes == 4) {
        std::memcpy(b.data.data() + static_cast<size_t>(r) * seq,
                    reinterpret_cast<const int32_t*>(base) + s,
                    static_cast<size_t>(seq) * 4);
      } else {
        for (int c = 0; c < seq; ++c)
          b.data[static_cast<size_t>(r) * seq + c] = token_at(s + c);
      }
    }
  }

  void worker() {
    while (!stop.load()) {
      std::vector<long> starts(batch);
      uint64_t ticket;
      {
        std::unique_lock<std::mutex> lk(mu);
        cv_full.wait(lk, [&] {
          return stop.load() || ring.size() + in_flight < ring_cap;
        });
        if (stop.load()) return;
        ticket = next_ticket++;
        ++in_flight;
        long span = n_tokens - seq;
        for (int r = 0; r < batch; ++r)
          starts[r] = span > 0 ? static_cast<long>(next_rand() % span) : 0;
      }
      Batch b;
      fill_batch(b, starts);
      {
        std::unique_lock<std::mutex> lk(mu);
        cv_order.wait(lk, [&] { return stop.load() || next_push == ticket; });
        if (stop.load()) return;
        ring.push_back(std::move(b));
        ++next_push;
        --in_flight;
        cv_empty.notify_one();
        cv_order.notify_all();
      }
    }
  }
};

}  // namespace

extern "C" {

void* kdl_loader_open(const char* path, int batch, int seq, uint64_t seed,
                      int prefetch, int token_bytes) {
  int fd = open(path, O_RDONLY);
  if (fd < 0) return nullptr;
  struct stat st;
  if (fstat(fd, &st) != 0 || st.st_size < token_bytes * (long)seq) {
    close(fd);
    return nullptr;
  }
  void* base = mmap(nullptr, st.st_size, PROT_READ, MAP_PRIVATE, fd, 0);
  if (base == MAP_FAILED) {
    close(fd);
    return nullptr;
  }
  auto* L = new Loader();
  L->fd = fd;
  L->base = static_cast<const uint8_t*>(base);
  L->file_bytes = st.st_size;
  L->token_bytes = token_bytes == 2 ? 2 : 4;
  L->n_tokens = st.st_size / L->token_bytes;
  L->batch = batch;
  L->seq = seq;
  L->rng = seed ? seed : 0x9E3779B97F4A7C15ULL;
  L->ring_cap = prefetch > 0 ? prefetch : 2;
  int n_threads = prefetch > 1 ? 2 : 1;
  for (int i = 0; i < n_threads; ++i)
    L->workers.emplace_back([L] { L->worker(); });
  return L;
}

long kdl_loader_tokens(void* h) {
  return h ? static_cast<Loader*>(h)->n_tokens : 0;
}

int kdl_loader_next(void* h, int32_t* out) {
  if (!h) return -1;
  auto* L = static_cast<Loader*>(h);
  Batch b;
  {
    std::unique_lock<std::mutex> lk(L->mu);
    L->cv_empty.wait(lk, [&] { return L->stop.load() || !L->ring.empty(); });
    if (L->stop.load()) return -1;
    b = std::move(L->ring.front());
    L->ring.pop_front();
    L->cv_full.notify_one();
  }
  std::memcpy(out, b.data.data(), b.data.size() * 4);
  return 0;
}

void kdl_loader_close(void* h) {
  delete static_cast<Loader*>(h);
}

}  // extern "C"
